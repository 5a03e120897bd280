"""Median device duration of one execution of the train-step program."""

META = {"unit": "ms", "better": "lower", "source": "device_trace",
        "layer": "step program", "moves": "train_tok_per_s_per_chip"}
TRAIN_STEP = "jit_train_step"


def read(result):
    trace = result.get("trace")
    if not trace or TRAIN_STEP not in trace["modules"]:
        return None
    return trace["modules"][TRAIN_STEP]["median_s"] * 1000.0
