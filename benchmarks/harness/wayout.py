"""The way out of a streamed token and the way in of the next request, from
the spans the replica's event loop writes into the profiler's trace
(``harness/hostplane.py`` loads them; what the readers of
``layer_metrics/stream_*``, ``replica_loop_lag_p50_ms`` and
``engine_queue_wait_p50_ms`` share). Not a metric: it has no ``META`` and
``BENCHMARK.json`` does not name it.

A step's tokens leave the engine's stepping thread in one ``post``
(``engine.deliver``) and are then the replica loop's:

    replica.fan_out      post_lag_us     the post -> the loop's callback
    replica.stream_item  rtt_us          one item packed, sent to its owner
                                         and acknowledged
    replica.stream_end   inbox_wait_us   once a request: the post of its
                                         result -> the stream's coroutine
                                         takes it, having awaited the
                                         acknowledgements of what came before
    replica.stream_open  admit_wait_us   the next request through the
                                         replica's own admission
    engine.admit         queue_wait_us   submitted -> a slot takes it
                         slot_free_us    how long that slot had been nobody's

In a closed loop a client is in the engine or on its way round (the engine
finished its answer, its next request has no slot yet), so by Little's law
a turn's mean is the clients outside the engine over the admissions a
second: (clients - mean live rows) / rate. The links' means add up to the
part of it the spans see; the rest is the caller's process and the
request's RPC in, which no profiler session covers. A *slot's* free time
(``slot_free_us``) is another quantity: the slot an answer leaves is taken
by whichever client's request comes next, lowest index first. Medians do
not add; means are printed beside them.

    python -m benchmarks.harness.wayout <file.xplane.pb> [clients]

An acknowledgement says the owner's process has the item, not that its
consumer has read it: how far behind it a client reads a token is the
client's clock's to say (``records``), and what this table leaves to the
caller's process is a subtraction.

A program that opens none of the replica's spans (the parent of the PR that
added them) gives empty lists, and every reader built on this returns None.
"""

from __future__ import annotations

import sys
from collections import defaultdict
from typing import Dict, List, Optional

from . import hostplane
from .stats import percentile

FAN_OUT, ITEM, END, OPEN = (
    "replica.fan_out", "replica.stream_item", "replica.stream_end",
    "replica.stream_open")
ADMIT = "engine.admit"


def _ms(values: List[float]) -> List[float]:
    return [us / 1000.0 for us in values]


def count_ms(loaded: dict, span: str, key: str, q: float = 50.0) -> Optional[float]:
    """Percentile ``q`` of the count ``key`` (microseconds) over the spans
    named ``span``, in milliseconds; None where there is no such count."""
    return percentile(_ms(hostplane.counts(loaded, span, key)), q)


def last_of_request(loaded: dict, span: str) -> List[dict]:
    """Of the spans named ``span``, each request's last (spans are by
    start): an admission the pool held back is tried again every step."""
    last: Dict[int, dict] = {}
    for s in hostplane.named(loaded, span):
        if "request_id" in s["stats"]:
            last[s["stats"]["request_id"]] = s
    return list(last.values())


def end_lags_ms(loaded: dict) -> List[float]:
    """``inbox_wait_us`` of the deliveries that carry a result: how late a
    finished answer leaves the replica."""
    return _ms(hostplane.counts(loaded, END, "inbox_wait_us"))


def queue_waits_ms(loaded: dict) -> List[float]:
    """``queue_wait_us`` of each request's last admission."""
    return _ms([s["stats"]["queue_wait_us"] for s in last_of_request(loaded, ADMIT)
                if "queue_wait_us" in s["stats"]])


def slot_frees_ms(loaded: dict) -> List[float]:
    """``slot_free_us`` of each request's last admission, a slot's first
    (written as 0) left out."""
    return _ms([s["stats"]["slot_free_us"] for s in last_of_request(loaded, ADMIT)
                if s["stats"].get("slot_free_us", 0) > 0])


def chain(loaded: dict) -> List[tuple]:
    """[(link, values in ms)] from a finished answer to its slot's next
    request, in the order a client's turn goes through them. The items
    behind an end are its delivery's tokens and the summary."""
    rtts = _ms(hostplane.counts(loaded, ITEM, "rtt_us"))
    mean_rtt = sum(rtts) / len(rtts) if rtts else 0.0
    return [
        ("loop lag (fan_out.post_lag_us)",
         _ms(hostplane.counts(loaded, FAN_OUT, "post_lag_us"))),
        ("the end's inbox wait (stream_end.inbox_wait_us)", end_lags_ms(loaded)),
        ("its items' round trips",
         [(tokens + 1) * mean_rtt for tokens in hostplane.counts(loaded, END, "tokens")]),
        ("replica admission (stream_open.admit_wait_us)",
         _ms(hostplane.counts(loaded, OPEN, "admit_wait_us"))),
        ("engine queue (admit.queue_wait_us)", queue_waits_ms(loaded)),
    ]


def rtt_over_life_ms(loaded: dict) -> float:
    """The largest excess of a stream's summed ``rtt_us`` over the time from
    its first acknowledgement to its last, the first item's own trip left
    out: never positive if a round trip is inside its stream's life."""
    items: Dict[int, list] = defaultdict(list)
    for s in hostplane.named(loaded, ITEM):
        items[s["stats"]["stream"]].append(s)
    return max(
        [sum(s["stats"]["rtt_us"] for s in acks[1:]) / 1000.0
         - (acks[-1]["start"] - acks[0]["start"]) / (hostplane.PS * 1e6)
         for acks in items.values()], default=0.0)


def turn(loaded: dict, clients: int) -> Optional[dict]:
    """A client's mean turn in a closed loop of ``clients``, by Little's
    law over the traced steps, the part of it the links account for, and
    what is left to the caller's process and the request's RPC; ms."""
    steps = hostplane.named(loaded, hostplane.STEP)
    live = hostplane.counts(loaded, "engine.decode_dispatch", "batch")
    admitted = len(last_of_request(loaded, ADMIT))
    links = chain(loaded)
    if not steps or not live or not admitted or not all(v for _, v in links):
        return None
    seconds = (max(s["end"] for s in steps) - steps[0]["start"]) / (hostplane.PS * 1e9)
    mean = 1000.0 * (clients - sum(live) / len(live)) / (admitted / seconds)
    seen = sum(sum(values) / len(values) for _, values in links)
    return {"mean_ms": mean, "seen_ms": seen, "left_ms": mean - seen}


def _row(label: str, values: List[float]) -> str:
    if not values:
        return f"{label:<48} none"
    return (f"{label:<48} n={len(values):<6} p50={percentile(values, 50):>9.3f} "
            f"p90={percentile(values, 90):>9.3f} mean={sum(values) / len(values):>9.3f}")


def table(loaded: dict, clients: Optional[int] = None) -> str:
    lines = [
        "the way out, ms",
        _row("stream_item.rtt_us (every item)", _ms(hostplane.counts(loaded, ITEM, "rtt_us"))),
        _row("fan_out.streams", [float(n) for n in hostplane.counts(
            loaded, FAN_OUT, "streams")]),
        "",
        "a client's turn from a finished answer to its next slot, ms",
    ]
    lines += [_row(label, values) for label, values in chain(loaded)]
    lines.append(_row("a slot's free time (admit.slot_free_us)", slot_frees_ms(loaded)))
    whole = turn(loaded, clients) if clients else None
    if whole:
        lines.append(
            f"{clients} clients: a turn's mean {whole['mean_ms']:.3f}, the links' means "
            f"{whole['seen_ms']:.3f}, left to the caller and the RPC in "
            f"{whole['left_ms']:.3f} = {100 * whole['left_ms'] / whole['mean_ms']:.1f} %")
    lines += ["", "a stream's summed round trips over its life, largest: "
                  f"{rtt_over_life_ms(loaded):.3f}"]
    return "\n".join(lines)


if __name__ == "__main__":
    print(table(hostplane.load(sys.argv[1]), *map(int, sys.argv[2:3])))
