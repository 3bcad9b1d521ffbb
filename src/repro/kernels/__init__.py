"""Kernel families for the BW-Raft hot paths (DESIGN.md §8).

Each family is a kernel.py + ref.py + ops.py package: `raft_tick`
(follower log-match + append, commit majority, last-wins apply),
`leader_fanout` (the budgeted AppendEntries ship — THE leader
bottleneck), `group_digest` (the Multi-Raft grouped digest reduction),
and `ae_sync` (digest-tier anti-entropy rounds).  Kernels compile on
TPU and run through the Pallas interpreter on CPU; every op is
bit-identical to its frozen ref twin and to the XLA formulations in
`core/` (test invariant).
"""
from __future__ import annotations

import jax

BACKENDS = ("auto", "xla", "pallas")


def resolve_backend(backend: str) -> str:
    """The per-platform backend-auto rule (DESIGN.md §8): `"auto"`
    resolves to `"pallas"` on TPU — where the kernels compile and the
    flip is earned — and `"xla"` everywhere else (on CPU the kernels
    run through the Pallas interpreter, a correctness path, not a fast
    path; BENCH_tick.json marks such timings `interpreted`).
    `"xla"`/`"pallas"` pass through, so the knob stays overridable, and
    callers key their epoch caches on the RESOLVED backend so `"auto"`
    and its resolution share one compiled program."""
    assert backend in BACKENDS, backend
    if backend == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    return backend
