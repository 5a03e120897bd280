"""Bytes one cached position costs over all layers: the program's counter
``runtime_info()["kv"]["cache_bytes_per_token"]``, read off the live slot
cache's leaves at the end of the run (so another width or dtype of the
cache shows: 9216 for 8 layers of a 576-wide bf16 latent row, where K and V
a head at these head sizes would be 81920). What every slot, every pool
block and every step's attention bytes scale with."""

from ..harness import mla_counters

META = {"unit": "bytes", "better": "lower", "source": "program_counter",
        "layer": "KV manager", "moves": "tpot_p50_ms"}


def read(result):
    return mla_counters.cache_bytes_per_token(result)
