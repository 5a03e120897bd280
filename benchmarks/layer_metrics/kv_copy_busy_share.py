"""Share of the device's busy time spent moving keys and values between the
dense slot rows and the block pool. The module names were read by hand from
PR 22's first trace of ``mistral7b-chat-backlog``: ``jit_commit_impl``
(row -> pool, one execution a block, ``kvcache/manager.py``), and the
engine's two anonymous row programs, which XLA names ``jit__lambda``
(``_insert_row`` and ``_extract_row``, ``llm/engine.py``; 0.62 ms a row
extraction). The greedy sampler is an anonymous lambda too and cannot be
told apart by name: 4 us a step, under 0.01% of busy time, counted in until
the program names its programs (PERF.md, Open questions). ``copy_impl``,
``adopt_impl`` and the assemble/build/extract ``impl`` programs run only on
a prefix hit or a shipment and are listed for the cells that will have them.
"""


META = {"unit": "%", "better": "lower", "source": "device_trace",
        "layer": "KV manager", "moves": "tpot_p50_ms"}
MODULES = ("jit_commit_impl", "jit_copy_impl", "jit_adopt_impl", "jit_impl",
           "jit__lambda")


def read(result):
    trace = result.get("trace")
    if not trace or not trace["busy_s"]:
        return None
    total = sum(m["total_s"] for name, m in trace["modules"].items()
                if name in MODULES)
    return 100.0 * total / trace["busy_s"]
