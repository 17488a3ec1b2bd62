"""Wall-clock stage timing for the fused hot path (observability layer).

The grant path (`core.server` phase preparation, the stacked executables
in `core.batched` / `core.selection` / `core.delta`) records per-stage
wall-clock here: first launch (compile + warm) apart from steady state,
and the bytes a stage moves. Two readers use the records: the benchmark's
per-layer metrics, which take the stage totals over a measured window,
and an operator's profiler trace. `span` opens a
``jax.profiler.TraceAnnotation`` named ``ams.<stage>`` around the work it
records, so each stage shows on the profiler's clock beside the device's
operations, and the trace says what the host did while the device idled.

Stats are process-global (like the executable caches they instrument) and
keyed by ``(stage, key)``, where ``key`` carries the stage's shape, e.g.
``("train_fused", (B, K))``. Callers that want per-run numbers bracket
with `snapshot()` / `delta(snap)`. Single-threaded by construction, like
the engine. `set_enabled(False)` turns every `record` into a no-op and
every `span` into one shared null context (the clock reads at the call
sites are guarded by `enabled()`, so the disabled overhead is one
module-attr check per stage).
"""
from __future__ import annotations

import time

_ENABLED = True
PREFIX = "ams."  # a span's name in the profiler's trace: PREFIX + stage

# (stage, key) -> {"calls", "first_calls", "first_s", "steady_s", "nbytes"}
_STATS: dict = {}

_FIELDS = ("calls", "first_calls", "first_s", "steady_s", "nbytes")


def enabled() -> bool:
    return _ENABLED


def set_enabled(on: bool) -> None:
    global _ENABLED
    _ENABLED = bool(on)


def reset() -> None:
    _STATS.clear()


def record(stage: str, seconds: float, *, first: bool = False,
           key: tuple = (), nbytes: int = 0) -> None:
    """Attribute ``seconds`` of wall-clock to ``stage``. ``first=True``
    marks a first launch for this executable (compile + warm) — kept out of
    the steady-state bucket so short runs don't report compile time as
    throughput. ``key`` carries the stage's shape (e.g. (B, K) for a fused
    train launch); ``nbytes`` accumulates the bytes the stage moves."""
    if not _ENABLED:
        return
    k = (stage, tuple(key))
    e = _STATS.get(k)
    if e is None:
        e = _STATS[k] = {"calls": 0, "first_calls": 0,
                         "first_s": 0.0, "steady_s": 0.0, "nbytes": 0}
    e["calls"] += 1
    if first:
        e["first_calls"] += 1
        e["first_s"] += seconds
    else:
        e["steady_s"] += seconds
    e["nbytes"] += int(nbytes)


class _Span:
    """An enabled `span`: a profiler annotation around the work, and a
    `record` of its host seconds on exit. ``nbytes`` may be set inside the
    block, for a stage whose bytes are known only once it has run."""

    __slots__ = ("stage", "key", "nbytes", "first", "start", "_ann", "_t0")

    def __init__(self, stage, key, nbytes, first, start):
        self.stage, self.key, self.nbytes = stage, key, nbytes
        self.first, self.start = first, start

    def __enter__(self):
        import jax

        kw = {"nbytes": int(self.nbytes)} if self.nbytes else {}
        self._ann = jax.profiler.TraceAnnotation(PREFIX + self.stage, **kw)
        self._ann.__enter__()
        self._t0 = time.perf_counter() if self.start is None else self.start
        return self

    def __exit__(self, *exc):
        seconds = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        record(self.stage, seconds, first=self.first, key=self.key,
               nbytes=self.nbytes)
        return False


class _Off:
    """The span of disabled timing: opens no annotation, records nothing."""

    __slots__ = ()
    nbytes = property(lambda self: 0, lambda self, value: None)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_OFF = _Off()


def span(stage: str, *, key: tuple = (), nbytes: int = 0,
         first: bool = False, start: float | None = None):
    """Context manager that times one stage where its work happens.

    Enabled, it opens ``jax.profiler.TraceAnnotation("ams." + stage)``
    (carrying ``nbytes`` as an argument where given), so the stage lands
    in a profiler trace on the device's clock, and on exit records the
    block's host seconds with `record`. It never waits for the device:
    a stage that must cover its device work calls `block` inside the
    block; device time is otherwise the trace's to show. ``start`` (a
    ``time.perf_counter()`` reading) makes the record count from an
    earlier moment than the annotation opens, for a stage whose
    record covers work that other stages' spans already annotate.

    Disabled, it returns one shared null context: no annotation (about a
    microsecond per span even with no profiler running), no record."""
    if not _ENABLED:
        return _OFF
    return _Span(stage, tuple(key), nbytes, first, start)


def snapshot() -> dict:
    """Copy of the global stats — pair with `delta` to scope a run."""
    return {k: dict(v) for k, v in _STATS.items()}


def delta(snap: dict | None) -> dict:
    """Stats accumulated since ``snap`` (a `snapshot()` return); entries
    with no new calls are dropped."""
    snap = snap or {}
    out = {}
    for k, v in _STATS.items():
        base = snap.get(k)
        d = dict(v) if base is None else {f: v[f] - base[f] for f in _FIELDS}
        if d["calls"]:
            out[k] = d
    return out


def totals(stats: dict | None = None) -> dict:
    """Aggregate ``(stage, key)`` stats down to per-stage totals."""
    stats = _STATS if stats is None else stats
    out: dict = {}
    for (stage, _key), v in sorted(stats.items(),
                                   key=lambda kv: (kv[0][0], str(kv[0][1]))):
        e = out.setdefault(stage, {f: 0 for f in _FIELDS})
        for f in _FIELDS:
            e[f] += v[f]
    return out


def compile_s(stats: dict | None = None) -> float:
    """Total first-launch (compile + warm) seconds across all stages."""
    stats = _STATS if stats is None else stats
    return sum(v["first_s"] for v in stats.values())


def block(tree) -> None:
    """Synchronize: wait for every jax leaf in ``tree`` before reading the
    clock, so a stage's recorded time covers its execution, not just its
    dispatch."""
    import jax

    for leaf in jax.tree.leaves(tree):
        getattr(leaf, "block_until_ready", lambda: None)()
