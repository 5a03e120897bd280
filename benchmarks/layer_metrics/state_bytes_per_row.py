"""Bytes of per-row state with no sequence axis a slot row carries over
all layers, whatever the row's length: the program's counter
``runtime_info()["kv"]["state_bytes_per_row"]``, read off the live slot
cache's state leaves at the end of the run (so another width or dtype of
the state shows: 6 x (32 x 128 x 256 x 4 + 3 x 5120 x 2) for six Falcon-H1
blocks). What every slot and every step's state traffic scale with, beside
``kv_bytes_per_token`` for what grows with the row."""

from ..harness import ssm_counters

META = {"unit": "bytes", "better": "lower", "source": "program_counter",
        "layer": "KV manager", "moves": "tpot_p50_ms"}


def read(result):
    return ssm_counters.kept(result, "state_bytes_per_row") or None
