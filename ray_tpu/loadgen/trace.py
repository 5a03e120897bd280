"""Replayable traffic traces.

A trace is the unit of reproducibility for every scaling experiment: a
JSON document with a ``meta`` block (how it was synthesized) and a list of
request records, each carrying its arrival offset, request class, shared
prefix id, full token ids, and deadline. ``LoadGenerator.run`` replays a
trace against any target; ``LoadResult.to_trace`` round-trips a recorded
run back into a trace so real traffic can be captured once and replayed.

The bundled trace (``traces/ramp_burst_decay.json``, regenerable with
``python -m ray_tpu.loadgen.trace``) is the small ramp -> burst -> decay
profile ``tests/test_autoscale_serve.py`` replays against an autoscaled
deployment.
"""

from __future__ import annotations

import json
import os
from dataclasses import asdict, dataclass, field
from typing import Any, Dict, List, Optional

TRACE_VERSION = 1


@dataclass
class TraceRecord:
    """One scheduled request. ``t`` is seconds from trace start."""

    t: float
    cls: str = "default"
    prefix_id: int = 0
    token_ids: List[int] = field(default_factory=list)
    max_new_tokens: int = 16
    deadline_s: Optional[float] = None
    adapter_id: Optional[str] = None

    def payload(self) -> Dict[str, Any]:
        """The request body shipped to the target. Carrying ``token_ids``
        means prefix-affinity handles (prefix_affinity_tokens > 0) and the
        paged KV cache both see real shared prefixes; ``adapter_id`` rides
        along for multi-tenant LoRA traces so replicas resolve a slot
        lease per request."""
        body = {
            "token_ids": list(self.token_ids),
            "max_new_tokens": self.max_new_tokens,
        }
        if self.adapter_id is not None:
            body["adapter_id"] = self.adapter_id
        return body

    def as_dict(self) -> dict:
        return asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "TraceRecord":
        return cls(
            t=float(d["t"]),
            cls=d.get("cls", "default"),
            prefix_id=int(d.get("prefix_id", 0)),
            token_ids=list(d.get("token_ids", [])),
            max_new_tokens=int(d.get("max_new_tokens", 16)),
            deadline_s=d.get("deadline_s"),
            adapter_id=d.get("adapter_id"),
        )


@dataclass
class Trace:
    meta: Dict[str, Any] = field(default_factory=dict)
    requests: List[TraceRecord] = field(default_factory=list)

    @property
    def duration_s(self) -> float:
        return self.requests[-1].t if self.requests else 0.0

    def scaled(
        self, time_scale: float = 1.0, limit: Optional[int] = None
    ) -> "Trace":
        """Replay-speed / size adjustment: time_scale < 1 compresses the
        schedule (2x traffic at 0.5), limit truncates the request list."""
        reqs = self.requests[:limit] if limit else self.requests
        return Trace(
            meta={**self.meta, "time_scale": time_scale},
            requests=[
                TraceRecord(
                    t=r.t * time_scale,
                    cls=r.cls,
                    prefix_id=r.prefix_id,
                    token_ids=list(r.token_ids),
                    max_new_tokens=r.max_new_tokens,
                    deadline_s=r.deadline_s,
                    adapter_id=r.adapter_id,
                )
                for r in reqs
            ],
        )

    def as_dict(self) -> dict:
        return {
            "version": TRACE_VERSION,
            "meta": self.meta,
            "requests": [r.as_dict() for r in self.requests],
        }

    def save(self, path: str):
        with open(path, "w") as f:
            json.dump(self.as_dict(), f)
            f.write("\n")

    @classmethod
    def from_dict(cls, doc: dict) -> "Trace":
        return cls(
            meta=dict(doc.get("meta", {})),
            requests=[
                TraceRecord.from_dict(r) for r in doc.get("requests", [])
            ],
        )

    @classmethod
    def load(cls, path: str) -> "Trace":
        with open(path) as f:
            return cls.from_dict(json.load(f))


_TRACES_DIR = os.path.join(os.path.dirname(__file__), "traces")


def bundled_trace(name: str = "ramp_burst_decay") -> Trace:
    """Load a trace shipped with the package (bench + tests)."""
    path = os.path.join(_TRACES_DIR, f"{name}.json")
    if not os.path.exists(path):
        available = sorted(
            f[:-5] for f in os.listdir(_TRACES_DIR) if f.endswith(".json")
        ) if os.path.isdir(_TRACES_DIR) else []
        raise FileNotFoundError(
            f"no bundled trace {name!r}; available: {available}"
        )
    return Trace.load(path)


def _build_ramp_burst_decay() -> Trace:
    """The bundled closed-loop demo trace: ~12 s of ramp (0.5 -> 8 rps),
    burst (16 rps), decay (8 -> 0.5 rps); two request classes over
    Zipf-skewed shared prefixes. Deterministic: same seeds, same JSON."""
    from .arrival import BurstyRampArrivals
    from .workload import RequestClass, ZipfPrefixes, synthesize

    phases = [(4.0, 0.5, 8.0), (4.0, 16.0, 16.0), (4.0, 8.0, 0.5)]
    arrivals = BurstyRampArrivals(phases, seed=7)
    classes = [
        RequestClass("short", weight=0.8, prompt_tokens=24,
                     max_new_tokens=8, deadline_s=30.0),
        RequestClass("long", weight=0.2, prompt_tokens=96,
                     max_new_tokens=32, deadline_s=30.0),
    ]
    prefixes = ZipfPrefixes(
        num_prefixes=32, alpha=1.2, prefix_tokens=16, seed=7
    )
    trace = synthesize(arrivals.times(), classes, prefixes, seed=7)
    trace.meta.update(
        name="ramp_burst_decay", phases=phases, seed=7,
        classes=[c.name for c in classes],
    )
    return trace


if __name__ == "__main__":  # regenerate the bundled trace in place
    os.makedirs(_TRACES_DIR, exist_ok=True)
    out = os.path.join(_TRACES_DIR, "ramp_burst_decay.json")
    trace = _build_ramp_burst_decay()
    trace.save(out)
    print(f"{out}: {len(trace.requests)} requests over "
          f"{trace.duration_s:.1f}s")
