"""Largest share of the block pool in use, ``kvcache_stats()``
``blocks_in_use`` over ``capacity``, sampled each second. Guards sizing: at
100% admission waits for a release and time to first token shows it."""

META = {"unit": "%", "better": "lower", "source": "program_counter",
        "layer": "KV manager", "moves": "tpot_p50_ms"}


def read(result):
    samples = [s for s in result.get("pool") or [] if s["capacity"]]
    if not samples:
        return None
    return 100.0 * max(s["blocks_in_use"] / s["capacity"] for s in samples)
