"""Closed loop: a fixed number of clients, each sending its next request as
soon as the last one returned, with no think time. Callers that each wait
for a reply (offline batch inference through the serving path): a slow
system receives less load, so the number that counts is tokens a second.

Mix keys: ``clients_per_slot`` (clients = that x the configuration's
``max_batch_size``), ``ramp_s``, and what ``harness/traffic.py`` reads.
"""

from __future__ import annotations

import threading
import time

from ..harness import traffic
from .serve_common import run_serving


def _load(clients, mix, vocab, seed, start, end, clock):
    def client(i: int):
        for request in traffic.requests(mix, vocab, seed, stream=i):
            if clock() >= end or clients.cut.is_set():
                return
            clients.one(request, due=clock())

    threads = [
        threading.Thread(target=client, args=(i,), daemon=True)
        for i in range(mix["_clients"])
    ]
    for t in threads:
        t.start()
    time.sleep(max(end - clock(), 0))
    return threads


def run(run):
    mix = run.cell["traffic_file"]
    mix["_clients"] = int(
        mix["clients_per_slot"] * run.cell["config_file"]["serving"]["max_batch_size"])
    return run_serving(run, _load)
