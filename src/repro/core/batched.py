"""Fused cross-session training: stacked vmap/scan execution of train phases.

Every AMS session of a given task shares one pytree structure (the student),
so B co-resident sessions' training phases need not be B separate K-iteration
dispatch loops: stack their ``(params, opt_state, mask, replay_batches)``
along a leading session axis (struct-of-arrays), run ``jax.lax.scan`` over
the K inner iterations of a ``jax.vmap``-ed loss/masked-optimizer step, and
unstack the results back into per-session state. One compiled executable —
cached at module level by (loss fn, shape-dtype struct, K, optimizer,
hyperparameters) and therefore shared by every same-shaped session in the
process — replaces ``B x K`` separate launches.

Numerics: the stacked executable agrees with the sequential
``AMSSession.train_phase`` to float32 tolerance (vmap batches the
convolutions differently, and XLA fuses the optimizer math into the backward
pass). A *singleton* group therefore runs the sequential step code itself —
bitwise-identical to ``train_phase`` by construction — so fusing is a pure
opt-in: with coalescing disabled every phase is a singleton and nothing
changes, to the bit.

Execution mode: the K iterations either live inside the executable as a
``lax.scan`` (one launch per phase — the accelerator-friendly shape) or the
cached executable is the vmapped *step* with the K-loop in Python (XLA:CPU
runs while-loop bodies on a single thread, measured ~4x slower than the
same math dispatched step-by-step). ``mode="auto"`` (the default) settles
scan-vs-loop **empirically**: the first fused call for a compile key builds
both executables, times one real execution of each on the caller's own
stacked batch, keeps the winner, and caches the decision — a one-shot
microbenchmark per (backend, compile key) instead of a backend-name check,
so an accelerator whose scan lowering happens to be slow (or a CPU build
whose loop dispatch is) is measured, not assumed. ``set_exec_mode`` forces
either shape (benchmarks/tests); ``auto_mode_info`` exposes the measured
decisions.

Sharded execution (`train_phases_sharded`): everything above runs on jax's
*default* device, so co-resident groups granted to different `GPUPool`
slots still execute serially — the pool's per-device clocks are modeled,
not measured. With the pool's ``device_backend="jax"`` knob each slot
binds a concrete ``jax.Device`` (`launch.host_mesh` forces N of them on a
CPU host), and `train_phases_sharded` runs D groups' fused lifecycles
(train → stacked select → batched encode) on D devices at once: each
group's stacked inputs are ``jax.device_put`` onto its slot's device and
the SAME cached executables dispatch asynchronously — jit keeps one
compiled program per (device, compile key), so per-device results are
bit-identical to the single-device fused path. ``spmd=True`` instead
concatenates uniform groups along the session axis and makes ONE
GSPMD launch over a cached `launch.mesh.make_session_mesh` sharding
(`_SHARD_CACHE`, per (mesh devices, compile key) via jit's sharding-aware
executable cache); one launch, but numerics only to the PR-7 float32
tolerance contract (masks and wire bytes stay byte-identical). Per-device
and whole-batch wall-clock land in `core.timing` ("sharded_device" /
"train_sharded").

Stage records (`core.timing`): the host work around each launch is timed
where it happens, as sibling spans on the dispatching thread: ``place``
(stacking the group's state and moving its replay batches to the device,
with the batch bytes sent) and ``commit`` (pulling the losses, unstacking
the group's params, optimizer state and last update in one jitted launch,
and adopting each member's state), beside ``select_stacked``,
``train_fused`` and ``encode_stacked``; ``prepare`` (replay sampling) is
`AMSSession._prepare_phase_deferred`'s. Each is a profiler annotation
``ams.<stage>`` too, so a trace shows which of them the device waits on.
"""
from __future__ import annotations

import time
from collections import defaultdict
from concurrent.futures import ThreadPoolExecutor
from functools import partial
from typing import Hashable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import kernel_dispatch, selection, timing
from repro.core.delta import encode_delta_stack
from repro.core.kernel_dispatch import kernel_dispatch_info, set_kernel_mode
from repro.core.masked_adam import masked_adam_update, momentum_update

# ---------------------------------------------------------------------------
# struct-of-arrays stack / unstack
# ---------------------------------------------------------------------------


@jax.jit
def _stack_impl(trees: tuple):
    return jax.tree.map(lambda *ls: jnp.stack(ls), *trees)


def stack_trees(trees: list):
    """Stack B same-structure pytrees along a new leading session axis.

    Jitted: the whole tree stacks in ONE launch (compile-cached by
    structure/shape) instead of one `jnp.stack` dispatch per leaf — at
    fleet scale the per-leaf dispatch overhead was most of the cost of
    assembling a stacked selection launch."""
    if not trees:
        raise ValueError("stack_trees needs at least one tree")
    return _stack_impl(tuple(trees))


@partial(jax.jit, static_argnums=1)
def _unstack_impl(tree, n: int) -> tuple:
    return tuple(jax.tree.map(lambda l: l[i], tree) for i in range(n))


def unstack_tree(tree, n: int) -> list:
    """Inverse of `stack_trees`: split the leading axis back into B trees.

    Jitted like the stack: every leaf's B slices come from ONE launch
    (compile-cached by structure/shape and ``n``) instead of one eager
    indexing dispatch per leaf per session — B x 657 dispatches for the
    MobileNetV2 student's params, Adam state and last update. A slice is a
    copy, so each output leaf is bit-identical to ``l[i]``; the launch
    follows its committed inputs onto their device or sharding."""
    return list(_unstack_impl(tree, n))


def _dtype_name(leaf) -> str:
    # leaves are jax/numpy arrays with a .dtype attribute; the asarray
    # fallback (python scalars) is kept off the hot path — going through
    # jnp.asarray for every leaf dominated the compile-key cost at scale
    dt = getattr(leaf, "dtype", None)
    return dt.name if dt is not None else np.asarray(leaf).dtype.name


def tree_struct(tree) -> Hashable:
    """Hashable shape/dtype/structure fingerprint of a pytree — the part of
    a compile key that decides whether two sessions can share an executable."""
    leaves, treedef = jax.tree.flatten(tree)
    return (treedef,
            tuple((tuple(l.shape), _dtype_name(l)) for l in leaves))


# ---------------------------------------------------------------------------
# module-level fused-phase executable cache
# ---------------------------------------------------------------------------

_PHASE_CACHE: dict = {}
_HITS = 0
_MISSES = 0

_EXEC_MODE = "auto"  # "auto" | "scan" | "loop"
# measured scan-vs-loop winners: (backend, base compile key) -> mode.
# "auto" consults this instead of the backend name; each entry is settled
# by a one-shot timed race of both executables on the first real call.
_AUTO_MODES: dict = {}


def set_exec_mode(mode: str) -> None:
    """Force the phase-executable shape: ``scan`` (K iterations inside one
    ``lax.scan`` launch), ``loop`` (one vmapped-step launch per iteration),
    or ``auto`` (first fused call per compile key races both and keeps the
    measured winner). Cached executables for the other mode are kept; the
    key includes the resolved mode."""
    if mode not in ("auto", "scan", "loop"):
        raise ValueError(f"exec mode must be auto|scan|loop, got {mode!r}")
    global _EXEC_MODE
    _EXEC_MODE = mode


def auto_mode_info() -> dict:
    """The measured auto decisions: {(backend, compile key): "scan"|"loop"}.
    Empty until an ``auto``-mode fused call has raced the two shapes."""
    return dict(_AUTO_MODES)


def cache_info() -> dict:
    """Hook for tests/telemetry: how often did sessions share an executable?"""
    return {"size": len(_PHASE_CACHE), "hits": _HITS, "misses": _MISSES}


def cache_clear() -> None:
    global _HITS, _MISSES
    _PHASE_CACHE.clear()
    _AUTO_MODES.clear()
    _HITS = _MISSES = 0


def _build_phase_fn(loss_and_grad, optimizer: str, lr: float, b1: float,
                    b2: float, eps: float, momentum: float, mode: str,
                    kernel: str = "xla"):
    """The fused executable: K iterations of a vmapped step.

    Signature: ``(params, opt_state, mask, frames, labels)`` where every tree
    leaf carries a leading session axis B and frames/labels are shaped
    ``(K, B, batch, ...)``. Returns stacked ``(params, opt_state, u_last,
    loss_last)`` with ``loss_last`` of shape (B,). ``mode="scan"`` compiles
    the whole phase into one launch; ``mode="loop"`` compiles the step once
    and dispatches it K times (see module docstring for why CPU wants this).

    ``kernel="pallas"`` (adam only) swaps the per-leaf tree_map optimizer
    for the fused Pallas kernel: the loss/grad stays a plain ``jax.vmap``,
    but the masked-Adam step runs as one `pl.pallas_call` per param dtype
    over flattened-and-concatenated ``(B, rows, 128)`` buffers — the
    session axis is a kernel grid dimension, and p/g/m/v/mask stream
    through VMEM exactly once per iteration
    (`repro.kernels.masked_adam.ops.masked_adam_stacked`). The unstack is
    bit-exact; the arithmetic agrees with the XLA path to float32 rounding
    (XLA's context-dependent FMA contraction can move single ULPs — the
    same caveat as scan-vs-loop, and it makes even the XLA path differ
    jit-vs-nojit), so the downstream selection masks and packed wire masks
    are byte-identical and the fp16 delta values agree to 1 ULP —
    CI-asserted (`scripts/ci.sh --kernels`).
    """

    def step(p, st, m, f, l):
        loss, grads = loss_and_grad(p, f, l)
        if optimizer == "adam":
            p, st, u = masked_adam_update(p, grads, st, m,
                                          lr=lr, b1=b1, b2=b2, eps=eps)
        else:
            p, st, u = momentum_update(p, grads, st, m,
                                       lr=lr, momentum=momentum)
        return p, st, u, loss

    if kernel == "pallas" and optimizer == "adam":
        from repro.kernels.masked_adam.ops import masked_adam_stacked

        vgrad = jax.vmap(lambda p, f, l: loss_and_grad(p, f, l))

        def vstep(p, st, m, f, l):
            loss, grads = vgrad(p, f, l)
            p, st, u = masked_adam_stacked(p, grads, st, m,
                                           lr=lr, b1=b1, b2=b2, eps=eps)
            return p, st, u, loss
    else:
        vstep = jax.vmap(step)

    if mode == "loop":
        jstep = jax.jit(vstep)

        def phase(params, opt_state, mask, frames, labels):
            for k in range(frames.shape[0]):
                params, opt_state, u, loss = jstep(params, opt_state, mask,
                                                   frames[k], labels[k])
            return params, opt_state, u, loss

        return phase

    @jax.jit
    def phase(params, opt_state, mask, frames, labels):
        u0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)

        def body(carry, xs):
            p, st, _ = carry
            f, l = xs
            p, st, u, loss = vstep(p, st, mask, f, l)
            return (p, st, u), loss

        (params, opt_state, u), losses = jax.lax.scan(
            body, (params, opt_state, u0), (frames, labels))
        return params, opt_state, u, losses[-1]

    return phase


def _block(tree) -> None:
    for leaf in jax.tree.leaves(tree):
        getattr(leaf, "block_until_ready", lambda: None)()


def _resolved_kernel(optimizer: str, base_key) -> str | None:
    """The kernel implementation the cached executable should embed:
    ``xla`` | ``pallas``, or None when ``kernel_mode("auto")`` has not yet
    raced this (backend, compile key). Non-adam optimizers have no Pallas
    implementation and always resolve to ``xla``."""
    if optimizer != "adam":
        return "xla"
    km = kernel_dispatch.kernel_mode()
    if km != "auto":
        return km
    return kernel_dispatch.auto_winner("train_fused", jax.default_backend(),
                                       base_key)


def fused_phase_fn(loss_and_grad, *, struct: Hashable, k_iters: int,
                   optimizer: str, lr: float, b1: float, b2: float,
                   eps: float, momentum: float):
    """The cached stacked-phase executable for one compile key.

    Keyed by the loss callable itself (sessions built from the same task
    share it — see `sim.seg_world`'s per-config compile cache), the stacked
    shape-dtype struct, K, and the optimizer recipe: N same-shaped sessions
    cost one compile, not N.

    Two independent axes settle ``auto`` decisions by one-shot timed races
    on the first real stacked batch, each recorded per (backend, compile
    key):

    * exec mode (``set_exec_mode``): scan-vs-loop, as before — the racer
      builds both executables, times one warmed execution of each, records
      the winner in `_AUTO_MODES` and caches its executable.
    * kernel mode (``set_kernel_mode``): XLA tree_map vs the fused Pallas
      masked-Adam, raced only AFTER the exec shape is settled (the exec
      race runs with the XLA kernel, so a default ``kernel_mode("xla")``
      process is bit-identical to the pre-dispatch code). The winner lands
      in `core.kernel_dispatch` (see `kernel_dispatch_info`).

    Each race is one cache miss; losers are discarded uncounted."""
    global _HITS, _MISSES
    base_key = (loss_and_grad, struct, k_iters, optimizer, lr, b1, b2, eps,
                momentum)
    backend = jax.default_backend()
    if _EXEC_MODE != "auto":
        mode = _EXEC_MODE
    else:
        mode = _AUTO_MODES.get((backend, base_key))
    kern = _resolved_kernel(optimizer, base_key)
    if mode is not None and kern is not None:
        key = base_key + (mode, kern)
        fn = _PHASE_CACHE.get(key)
        if fn is None:
            _MISSES += 1
            fn = _build_phase_fn(loss_and_grad, optimizer, lr, b1, b2, eps,
                                 momentum, mode, kern)
            _PHASE_CACHE[key] = fn
        else:
            _HITS += 1
        return fn
    _MISSES += 1

    def _timed_best(fn, args):
        _block(fn(*args))  # compile + warm, excluded from the clock
        best, out = float("inf"), None
        for _ in range(2):  # best-of-2: damp scheduler/GC jitter
            t0 = time.perf_counter()
            out = fn(*args)
            _block(out)
            best = min(best, time.perf_counter() - t0)
        return best, out

    if mode is None:
        # exec-shape race (kernel pinned: resolved if decided, else the
        # XLA reference — so the exec decision never depends on an
        # unraced kernel axis)
        kern0 = kern if kern is not None else "xla"

        def race(params, opt_state, mask, frames, labels):
            args = (params, opt_state, mask, frames, labels)
            outs, times = {}, {}
            for m in ("loop", "scan"):
                fn = _build_phase_fn(loss_and_grad, optimizer, lr, b1, b2,
                                     eps, momentum, m, kern0)
                times[m], out = _timed_best(fn, args)
                outs[m] = (fn, out)
            # ties break lexically ("loop"); note the race is wall-clock —
            # a near-tie can resolve differently across processes, and the
            # two shapes agree only to float32 tolerance (forced modes, or
            # a pre-warmed cache, give bit-stable numerics when needed)
            winner = min(times, key=lambda m: (times[m], m))
            _AUTO_MODES[(backend, base_key)] = winner
            _PHASE_CACHE[base_key + (winner, kern0)] = outs[winner][0]
            return outs[winner][1]

        return race

    def krace(params, opt_state, mask, frames, labels):
        # XLA-vs-Pallas race at the settled exec shape. Both paths produce
        # byte-identical selection masks and wire masks (CI-asserted); the
        # fp16 delta values agree to 1 ULP — the residue of XLA:CPU's
        # context-dependent FMA contraction, which makes even the XLA
        # reference differ jit-vs-nojit (see `_build_phase_fn`).
        args = (params, opt_state, mask, frames, labels)
        outs, times = {}, {}
        for kn in ("xla", "pallas"):
            fn = _PHASE_CACHE.get(base_key + (mode, kn))
            if fn is None:
                fn = _build_phase_fn(loss_and_grad, optimizer, lr, b1, b2,
                                     eps, momentum, mode, kn)
            times[kn], out = _timed_best(fn, args)
            outs[kn] = (fn, out)
        winner = min(times, key=lambda kn: (times[kn], kn))
        kernel_dispatch.record_auto("train_fused", backend, base_key,
                                    winner, times)
        _PHASE_CACHE[base_key + (mode, winner)] = outs[winner][0]
        return outs[winner][1]

    return krace


# ---------------------------------------------------------------------------
# fused phase over live sessions
# ---------------------------------------------------------------------------

# update-pipeline telemetry: how much of the post-train select/encode work
# ran stacked (one launch / one transfer pair per fused group) instead of
# per-session. The serving engine snapshots this around a run.
_UPDATE_STATS = {"stacked_select_launches": 0, "stacked_select_sessions": 0,
                 "stacked_encode_launches": 0, "stacked_encode_sessions": 0,
                 "stacked_unstack_launches": 0,
                 "stacked_unstack_sessions": 0}


def update_pipeline_info() -> dict:
    """Counters for the fused post-train update pipeline (stacked selection
    launches + batched delta encodes and the sessions they covered), plus
    the commit's unstack: ``stacked_unstack_launches`` (one per stacked
    commit) and ``stacked_unstack_sessions`` (the states it handed back)."""
    return dict(_UPDATE_STATS)


def update_pipeline_reset() -> None:
    for k in _UPDATE_STATS:
        _UPDATE_STATS[k] = 0


def _mask_struct(s, mask) -> Hashable:
    """Shape fingerprint of the phase's mask tree. A deferred gradient-
    guided mask (None) has param-shaped bool leaves by construction, so its
    struct is derivable without materializing it."""
    if mask is not None:
        return tree_struct(mask)
    leaves, treedef = jax.tree.flatten(s.params)
    return (treedef, tuple((tuple(l.shape), "bool") for l in leaves))


def _group_key(s, mask, frames, labels) -> Hashable:
    cfg = s.cfg
    return (s.task.loss_and_grad,
            tree_struct((s.params, s.opt_state)), _mask_struct(s, mask),
            cfg.k_iters, cfg.optimizer, cfg.lr, cfg.b1, cfg.b2, cfg.eps,
            cfg.momentum,
            # the update pipeline batches selection (keyed by γ/strategy)
            # and delta encode (keyed by wire dtype) across the group, so
            # they must agree for sessions to share a fused launch
            cfg.strategy, cfg.gamma, cfg.value_dtype,
            tuple(frames.shape), str(frames.dtype),
            tuple(labels.shape), str(labels.dtype))


def _stacked_masks(members, force_stack: bool, device=None):
    """The group's stacked mask tree, batching deferred gradient-guided
    selections into one vmapped launch.

    ``members`` carry mask=None where selection was deferred
    (`AMSSession._select_mask_or_defer`); those sessions' ``u_prev`` trees
    stack into a single `selection.stacked_gradient_guided_masks` call —
    B thresholds + B mask trees from one executable instead of B solo
    bisections. Concrete masks (first-phase random, Table-3 ablations)
    stack as-is; a mixed group re-stacks device-side slices (no host
    round-trip).

    ``device`` (a ``jax.Device`` or `Sharding`, sharded path only) places
    the selection on the group's own pool device: an all-deferred group
    moves the stacked ``u_prev`` there so the bisection launch itself runs
    on-device; mixed groups select on the default device and only the
    final stacked mask moves. None (the default) touches placement not at
    all — bit-identical to the pre-sharding code."""
    deferred = [j for j, m in enumerate(members) if m[2] is None]
    gamma = members[0][1].cfg.gamma
    if len(deferred) >= 2 or (deferred and force_stack):
        u_stack = stack_trees([members[j][1].u_prev for j in deferred])
        pure = len(deferred) == len(members)
        if device is not None and pure:
            u_stack = jax.device_put(u_stack, device)
        stacked_d = selection.stacked_gradient_guided_masks(u_stack, gamma)
        _UPDATE_STATS["stacked_select_launches"] += 1
        _UPDATE_STATS["stacked_select_sessions"] += len(deferred)
        if pure:
            return stacked_d
        per = dict(zip(deferred, unstack_tree(stacked_d, len(deferred))))
    else:
        per = {j: selection.gradient_guided_mask(members[j][1].u_prev, gamma)
               for j in deferred}
    masks = [per.get(j, m[2]) for j, m in enumerate(members)]
    out = stack_trees(masks)
    return jax.device_put(out, device) if device is not None else out


def train_phases_fused(sessions: list, t_now: float,
                       force_stack: bool = False, device=None) -> list:
    """Run one training phase for several sessions as fused launches.

    Per-session host-side work (replay sampling, ASR/ATR bookkeeping)
    happens in session order, consuming each session's RNG streams exactly
    as its own ``train_phase`` would. Sessions that share a compile key —
    same loss callable, shapes, K, optimizer, selection/wire recipe — are
    stacked and executed as ONE scan/vmap launch; a session with nothing to
    train yields None in its slot, exactly like ``train_phase``.

    The post-train update pipeline is fused too: the group's gradient-guided
    selections run as one stacked bisection launch (`core.selection`), and
    the B wire deltas come from one batched device->host encode
    (`delta.encode_delta_stack`, byte-identical to per-session encoding) —
    no per-session serial stage is left between the fused launch and the
    deltas.

    Singleton groups take the sequential step path (bitwise-identical to
    ``train_phase``); pass ``force_stack=True`` to push even B=1 through the
    stacked executable (benchmarks/tests only).

    ``device`` places each stacked group's lifecycle on a concrete
    ``jax.Device`` (the pool slot's binding under
    ``GPUPool(device_backend="jax")``). Identical jitted programs on
    same-kind devices produce bit-identical results, so this moves *where*
    the math runs, not what it computes; the sequential singleton path
    ignores it (its contract is bitwise equality with ``train_phase`` on
    the default device). None — the default — performs zero placements.
    """
    results: dict[int, object] = {}
    groups: dict[Hashable, list] = defaultdict(list)
    for i, s in enumerate(sessions):
        prep = s._prepare_phase_deferred(t_now)
        if prep is None:
            results[i] = None
            continue
        mask, frames, labels = prep  # mask None = deferred gradient-guided
        groups[_group_key(s, mask, frames, labels)].append(
            (i, s, mask, frames, labels))

    for members in groups.values():
        if len(members) == 1 and not force_stack:
            i, s, mask, frames, labels = members[0]
            if mask is None:
                mask = selection.gradient_guided_mask(s.u_prev, s.cfg.gamma)
            results[i] = s._run_phase_prepared(t_now, mask, frames, labels)
            continue
        out, _ = _launch_stacked(members, device=device)
        _commit_stacked(members, t_now, out, results)
    return [results[i] for i in range(len(sessions))]


def _batch_spec(device):
    """Placement for scan-major ``(K, B, ...)`` batches: a session
    `NamedSharding` names axis 0, but frames/labels carry the session axis
    at position 1 — shift the spec; a plain Device places the whole leaf."""
    if isinstance(device, jax.sharding.NamedSharding):
        return jax.sharding.NamedSharding(
            device.mesh, jax.sharding.PartitionSpec(None, *device.spec))
    return device


def _launch_stacked(members, device=None, record=True):
    """Stack one compile-key group and dispatch its fused train launch.

    Returns ``((params, opt, u, losses, mask), first_launch)`` with the
    arrays still on device (dispatch is async — nothing here blocks unless
    timing is on, which needs the completed wall-clock; ``record=False``
    skips the stage record so `train_phases_sharded` can dispatch D groups
    without a serializing block and clock them itself). ``device`` may be
    a ``jax.Device`` or a `Sharding`; None keeps the default placement."""
    ss = [m[1] for m in members]
    mask = _stacked_masks(members, True, device=device)
    with _place_span(members):
        params = stack_trees([s.params for s in ss])
        opt = stack_trees([s.opt_state for s in ss])
        # batches: per-session (K, batch, ...) -> scan-major (K, B, batch, ...)
        frames = jnp.stack([m[3] for m in members], axis=1)
        labels = jnp.stack([m[4] for m in members], axis=1)
        if device is not None:
            # one placement per tree; the mask already lives there, and
            # every launch below follows its committed inputs onto it
            params, opt = jax.device_put((params, opt), device)
            frames, labels = jax.device_put((frames, labels),
                                            _batch_spec(device))
    s0 = ss[0]
    miss0 = _MISSES
    phase = fused_phase_fn(
        s0.task.loss_and_grad,
        struct=tree_struct((params, opt, mask)),
        k_iters=s0.cfg.k_iters, optimizer=s0.cfg.optimizer,
        lr=s0.cfg.lr, b1=s0.cfg.b1, b2=s0.cfg.b2, eps=s0.cfg.eps,
        momentum=s0.cfg.momentum)
    first = _MISSES > miss0
    if record and timing.enabled():
        # first launch (a cache miss — including the auto-mode race)
        # lands in the compile bucket, steady launches in steady-state.
        # nbytes: analytic optimizer-update traffic only (the
        # masked-Adam roofline term — forward/backward excluded),
        # B x K x `roofline.analysis.adam_step_hbm_bytes`
        with timing.span("train_fused", first=first,
                         key=(len(members), s0.cfg.k_iters),
                         nbytes=(len(members) * s0.cfg.k_iters * 33
                                 * selection.tree_size(s0.params))):
            params, opt, u, losses = phase(params, opt, mask, frames, labels)
            timing.block((params, opt, u, losses))
    else:
        params, opt, u, losses = phase(params, opt, mask, frames, labels)
    return (params, opt, u, losses, mask), first


def _place_span(members):
    """The ``place`` stage of one stacked launch: keyed by the stack's
    width B, counting the replay batches' bytes sent to the device."""
    return timing.span("place", key=(len(members),),
                       nbytes=sum(m[3].nbytes + m[4].nbytes
                                  for m in members))


# ---------------------------------------------------------------------------
# sharded execution: D co-resident groups on D real pool devices
# ---------------------------------------------------------------------------

# (mesh device ids) -> session NamedSharding for the one-launch SPMD path.
# The compiled sharded program itself is cached by jit, which keys
# executables by (sharding, compile key) — keeping the mesh object stable
# here is what lets that cache hit; rebuilding a Mesh per call would
# recompile every launch.
_SHARD_CACHE: dict = {}
_SHARD_STATS = {"batches": 0, "groups": 0, "sessions": 0,
                "dispatch_launches": 0, "spmd_launches": 0,
                "distinct_devices": 0}


def sharded_info() -> dict:
    """Counters for sharded batches: launches per path (per-device dispatch
    vs SPMD one-launch), groups/sessions covered, and the widest distinct-
    device fan-out actually achieved (1 on a one-device host — correctness
    holds but nothing ran in parallel)."""
    return dict(_SHARD_STATS)


def sharded_reset() -> None:
    for k in _SHARD_STATS:
        _SHARD_STATS[k] = 0


def _session_sharding(devices):
    """The cached 1-D session-axis NamedSharding over ``devices``."""
    key = tuple(id(d) for d in devices)
    hit = _SHARD_CACHE.get(key)
    if hit is None:
        mesh = jax.sharding.Mesh(np.array(devices), axis_names=("session",))
        hit = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("session"))
        _SHARD_CACHE[key] = hit
    return hit


def _launch_spmd(members, group_key, shard_b, sharding):
    """One `shard_map` launch covering D uniform co-resident groups.

    GSPMD cannot partition the vmapped phase along the session axis (vmap
    lowers the student's convolutions into feature-group form, and XLA
    refuses to split the group dimension), so the one-launch path maps
    instead: every mesh device runs the SAME per-group executable the
    dispatch path uses — shard width = the group's B — over its slice of
    the session-concatenated stacks. The per-group phase fn must be
    settled (its exec/kernel races decided) before it can be traced as a
    shard_map body; an unsettled key is raced once on shard 0's slice
    first, outputs discarded.

    Returns ``((params, opt, u, losses, mask), first_launch)`` like
    `_launch_stacked`, with every tree still sharded across the mesh."""
    from jax.experimental.shard_map import shard_map

    ss = [m[1] for m in members]
    mask = _stacked_masks(members, True, device=sharding)
    with _place_span(members):
        params = stack_trees([s.params for s in ss])
        opt = stack_trees([s.opt_state for s in ss])
        frames = jnp.stack([m[3] for m in members], axis=1)
        labels = jnp.stack([m[4] for m in members], axis=1)
        params, opt = jax.device_put((params, opt), sharding)
        frames, labels = jax.device_put((frames, labels),
                                        _batch_spec(sharding))
    s0 = ss[0]
    miss0 = _MISSES

    def shard_struct(t):
        return jax.tree.map(
            lambda l: jax.ShapeDtypeStruct((shard_b,) + l.shape[1:],
                                           l.dtype), t)

    struct = tree_struct((shard_struct(params), shard_struct(opt),
                          shard_struct(mask)))
    fkw = dict(struct=struct, k_iters=s0.cfg.k_iters,
               optimizer=s0.cfg.optimizer, lr=s0.cfg.lr, b1=s0.cfg.b1,
               b2=s0.cfg.b2, eps=s0.cfg.eps, momentum=s0.cfg.momentum)
    base = (s0.task.loss_and_grad, struct, s0.cfg.k_iters, s0.cfg.optimizer,
            s0.cfg.lr, s0.cfg.b1, s0.cfg.b2, s0.cfg.eps, s0.cfg.momentum)
    backend = jax.default_backend()
    settled = ((_EXEC_MODE != "auto" or (backend, base) in _AUTO_MODES)
               and _resolved_kernel(s0.cfg.optimizer, base) is not None)
    if not settled:
        p0, o0, m0 = jax.tree.map(lambda l: l[:shard_b],
                                  (params, opt, mask))
        fused_phase_fn(s0.task.loss_and_grad, **fkw)(
            p0, o0, m0, frames[:, :shard_b], labels[:, :shard_b])
    fn = fused_phase_fn(s0.task.loss_and_grad, **fkw)
    key = ("spmd", tuple(id(d) for d in sharding.mesh.devices.flat),
           group_key, shard_b, id(fn))
    wrapped = _SHARD_CACHE.get(key)
    first = wrapped is None or _MISSES > miss0
    if wrapped is None:
        spec = jax.sharding.PartitionSpec("session")
        batch_spec = jax.sharding.PartitionSpec(None, "session")
        wrapped = jax.jit(shard_map(
            fn, mesh=sharding.mesh,
            in_specs=(spec, spec, spec, batch_spec, batch_spec),
            out_specs=spec))
        _SHARD_CACHE[key] = wrapped
    params, opt, u, losses = wrapped(params, opt, mask, frames, labels)
    return (params, opt, u, losses, mask), first


def train_phases_sharded(session_groups: list, t_now: float, *,
                         devices: list, spmd: bool = False) -> list:
    """Run D co-resident groups' fused lifecycles on D pool devices at once.

    ``session_groups[g]`` is the member list of one granted pool slot (the
    sessions a fused grant would stack); ``devices[g]`` is that slot's
    ``jax.Device`` binding (`GPUPool.jax_devices()` under
    ``device_backend="jax"``). Host-side phase preparation runs in input
    order — the same RNG consumption as ``train_phases_fused`` over the
    concatenation — then every group's stacked train→select launch is
    placed on its own device and dispatched *asynchronously*: D devices
    compute concurrently, and one waiter thread per launch timestamps each
    device's own completion (``block_until_ready`` releases the GIL).
    Wire deltas and commits follow in group order, one batched
    device->host encode per group.

    A ``devices`` entry of None dispatches that group on the default
    device — passing all-None degrades to serial fused execution, which is
    exactly the baseline the `--sharded` benchmark clocks against. Each
    group must share ONE compile key (the engine only fuses same-key
    sessions onto a slot); mixed groups raise.

    ``spmd=True`` runs uniform groups (one compile key, equal B, concrete
    devices) as ONE `shard_map` launch instead: groups concatenate along
    the session axis, a cached `_session_sharding` mesh splits the stack
    across the devices, and every device runs the SAME per-group
    executable over its shard (`_launch_spmd`). One launch per lifecycle —
    the accelerator-friendly shape — but the collective-mapped program is
    a different executable from the solo one, so numerics carry the PR-7
    tolerance contract (masks/wire bytes byte-identical, fp16 within
    1 ULP) rather than the per-device dispatch path's bit-identity.

    Timing lands per device ("sharded_device", key=(slot, B, K)) and per
    batch ("train_sharded", from before the first launch to the last
    device's finish; its ``ams.train_sharded`` annotation covers only the
    wait, so the launches' own ``place`` and ``select_stacked`` spans stay
    its siblings in a trace). `obs.drift_report` folds both against the
    pool's `GPUCostModel` for the engine's results.

    Returns a list of per-group result lists (delta-or-None per session,
    ``train_phases_fused`` semantics)."""
    if len(devices) != len(session_groups):
        raise ValueError(
            f"{len(session_groups)} session groups need as many device "
            f"bindings, got {len(devices)}")
    results_per: list[dict] = [{} for _ in session_groups]
    prepped = []
    for gi, sessions in enumerate(session_groups):
        members, key0 = [], None
        for i, s in enumerate(sessions):
            prep = s._prepare_phase_deferred(t_now)
            if prep is None:
                results_per[gi][i] = None
                continue
            mask, frames, labels = prep
            k = _group_key(s, mask, frames, labels)
            if key0 is None:
                key0 = k
            elif k != key0:
                raise ValueError(
                    "a sharded group must share ONE compile key (the "
                    "engine fuses only same-key sessions onto a device); "
                    "split mixed sessions across slots")
            members.append((i, s, mask, frames, labels))
        if members:
            prepped.append((gi, members, key0))

    timing_on = timing.enabled()
    if prepped:
        _SHARD_STATS["batches"] += 1
        _SHARD_STATS["groups"] += len(prepped)
        _SHARD_STATS["sessions"] += sum(len(m) for _, m, _ in prepped)
        _SHARD_STATS["distinct_devices"] = max(
            _SHARD_STATS["distinct_devices"],
            len({id(devices[gi]) for gi, _, _ in prepped
                 if devices[gi] is not None}) or 1)
    t0 = time.perf_counter()

    if spmd and len(prepped) >= 2:
        if len({k for _, _, k in prepped}) != 1 \
                or len({len(m) for _, m, _ in prepped}) != 1:
            raise ValueError(
                "spmd one-launch needs uniform groups: one compile key and "
                "equal B on every device")
        devs = [devices[gi] for gi, _, _ in prepped]
        if any(d is None for d in devs):
            raise ValueError(
                "spmd needs a concrete jax.Device per group — build the "
                "pool with device_backend='jax'")
        # flatten to one big member list with synthetic flat indices, so
        # the shared commit tail can scatter results back per group
        flat, slots = [], []
        for gi, members, _ in prepped:
            for (i, s, m, f, l) in members:
                flat.append((len(flat), s, m, f, l))
                slots.append((gi, i))
        out, first = _launch_spmd(flat, prepped[0][2], len(prepped[0][1]),
                                  _session_sharding(devs))
        k0 = flat[0][1].cfg.k_iters
        with timing.span("train_sharded", first=first, start=t0,
                         key=(len(prepped), len(prepped[0][1]), k0),
                         nbytes=(len(flat) * k0 * 33
                                 * selection.tree_size(flat[0][1].params))):
            _block(out)
        _SHARD_STATS["spmd_launches"] += 1
        flat_results: dict = {}
        _commit_stacked(flat, t_now, out, flat_results)
        for j, (gi, i) in enumerate(slots):
            results_per[gi][i] = flat_results[j]
        return [[results_per[gi].get(i) for i in range(len(sg))]
                for gi, sg in enumerate(session_groups)]

    launches = []
    for gi, members, _ in prepped:
        out, first = _launch_stacked(members, device=devices[gi],
                                     record=False)
        launches.append((gi, members, out, first))
        _SHARD_STATS["dispatch_launches"] += 1
    if launches:
        # per-device completion clocks: one waiter thread per launch, each
        # timestamping its own device's finish (threads, not a serial
        # block loop — blocking on slot 0 first would fold slot 1's real
        # finish time into slot 0's wait)
        def _wait(out):
            _block(out)
            return time.perf_counter()

        bks = {(len(m), m[0][1].cfg.k_iters) for _, m, _, _ in launches}
        uniform = bks.pop() if len(bks) == 1 else None
        with timing.span("train_sharded", start=t0,
                         first=any(l[3] for l in launches),
                         key=(len(launches),) + (uniform or ()),
                         nbytes=sum(len(m) * m[0][1].cfg.k_iters * 33
                                    * selection.tree_size(m[0][1].params)
                                    for _, m, _, _ in launches)):
            if len(launches) > 1:
                with ThreadPoolExecutor(max_workers=len(launches)) as ex:
                    done = list(ex.map(_wait, [l[2] for l in launches]))
            else:
                done = [_wait(launches[0][2])]
        if timing_on:
            for (gi, members, out, first), t_done in zip(launches, done):
                s0 = members[0][1]
                timing.record(
                    "sharded_device", t_done - t0, first=first,
                    key=(gi, len(members), s0.cfg.k_iters),
                    nbytes=(len(members) * s0.cfg.k_iters * 33
                            * selection.tree_size(s0.params)))
    for gi, members, out, _ in launches:
        _commit_stacked(members, t_now, out, results_per[gi])
    return [[results_per[gi].get(i) for i in range(len(sg))]
            for gi, sg in enumerate(session_groups)]


def _commit_stacked(members, t_now, out, results) -> None:
    """Encode the group's wire deltas (one batched device->host pull) and
    commit per-member state — the tail every stacked launch shares. The
    commit (losses pulled, state unstacked and adopted) is its own stage,
    after the encode's; its unstack of ``(params, opt, u)`` is one jitted
    launch for the whole group."""
    params, opt, u, losses, mask = out
    b = len(members)
    s0 = members[0][1]
    deltas = encode_delta_stack(params, mask, b, s0.cfg.value_dtype)
    _UPDATE_STATS["stacked_encode_launches"] += 1
    _UPDATE_STATS["stacked_encode_sessions"] += b
    with timing.span("commit"):
        losses = np.asarray(losses)
        states = unstack_tree((params, opt, u), b)
        _UPDATE_STATS["stacked_unstack_launches"] += 1
        _UPDATE_STATS["stacked_unstack_sessions"] += b
        for j, (i, s, _, _, _), (p_j, o_j, u_j) in zip(
                range(b), members, states):
            # the delta is already encoded (batched), so no per-member
            # mask slice is ever consumed — don't dispatch B tree-slicings
            results[i] = s._commit_phase(t_now, p_j, o_j, u_j,
                                         float(losses[j]), None,
                                         delta=deltas[j])
