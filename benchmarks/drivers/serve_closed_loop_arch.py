"""Closed loop (``serve_closed_loop.py``: a fixed number of clients, each
sending its next request as soon as the last one returned) for a
configuration whose ``architecture`` key names its plain reference: the
model is whatever family that reference's ``llm_arguments`` names, not
``Llama``. ``serve_arch_common.py`` says how an architecture is added.

Mix keys: those of ``serve_closed_loop``.
"""

from __future__ import annotations

from .serve_arch_common import run_serving
from .serve_closed_loop import _load


def run(run):
    mix = run.cell["traffic_file"]
    mix["_clients"] = int(
        mix["clients_per_slot"] * run.cell["config_file"]["serving"]["max_batch_size"])
    return run_serving(run, _load)
