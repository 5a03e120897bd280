"""Bytes of window rings a slot row carries over all layers, whatever the
row's length: the program's counter
``runtime_info()["kv"]["window_bytes_per_row"]``, read off the live slot
cache's ring leaves at the end of the run (6 x 128 x (512 + 64) x 2 for six
window layers of a bf16 latent row), beside ``kv_bytes_per_token`` for what
grows with the row (the full layers only). None for a program without the
counter, and for a family that keeps no ring (0)."""

from ..harness import ssm_counters

META = {"unit": "bytes", "better": "lower", "source": "program_counter",
        "layer": "KV manager", "moves": "tpot_p50_ms"}


def read(result):
    return ssm_counters.kept(result, "window_bytes_per_row") or None
