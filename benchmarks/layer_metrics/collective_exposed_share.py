"""Share of the traced window a device's core spent inside collective ops
(all-gather, all-reduce, reduce-scatter, ... and the ``-done`` halves of
asynchronous ones), on the worst device. The core runs one op at a time, so
while it sits in a collective no compute runs there: this is the part of
communication not hidden behind compute."""

META = {"unit": "%", "better": "lower", "source": "device_trace",
        "layer": "collective", "moves": "train_tok_per_s_per_chip"}


def read(result):
    trace, traced = result.get("trace"), result.get("traced")
    if not trace or not traced or trace["devices"] < 2:
        return None
    window = traced["stop"] - traced["start"]
    return 100.0 * max(trace["collective_self_s"]) / window
