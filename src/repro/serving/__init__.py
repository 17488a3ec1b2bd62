"""Event-driven AMS serving runtime — many edge devices, a GPU pool, a real(ish) network.

Paper-concept -> class map (Appendix D/E):

  ==========================================  =================================
  Paper concept                               Here
  ==========================================  =================================
  Shared-GPU round-robin (App. E)             `policies.FairRoundRobin`
  Deferred phases under saturation (Fig. 6)   `engine.ServingEngine` backlog +
                                              admission control / drop stats
  ATR cycle reclamation (App. D)              `policies.GainAware` (recent
                                              φ-score + staleness priority,
                                              φ-aware eviction when saturated)
  App. E scaling argument, many GPUs          `resources.GPUPool` (per-device
                                              stream clocks + session
                                              residency) + `policies.
                                              AffinityAware` (session, gpu)
                                              placement
  Server labels + trains concurrently (§4)    `resources.StreamModel`: label
                                              vs train streams per device,
                                              overlap with bounded slowdown,
                                              labeling preemptible at frame-
                                              batch boundaries
  Uplink frame batches / downlink deltas      `network.ClientNetwork` (links
  (§3.1.2, §3.2, Tables 1-2)                  occupy `bytes/rate` s, feed the
                                              per-client `BandwidthLedger`)
  Edge double-buffered swap (§3)              via `session.SegServingSession`
                                              wrapping `core.client.EdgeClient`
  ==========================================  =================================

Quickstart::

    from repro.serving import (LinkSpec, ClientNetwork, SegServingSession,
                               ServingEngine, ServingConfig)

    sessions = [
        SegServingSession(i, world_i, ams_session_i, pretrained,
                          net=ClientNetwork(LinkSpec(up_kbps=500,
                                                     down_kbps=2000)))
        for i, (world_i, ams_session_i) in enumerate(zip(worlds, ams))
    ]
    result = ServingEngine(sessions, policy="affinity",
                           cfg=ServingConfig(duration=120.0, n_gpus=4)).run()
    print(result["mean_miou"], result["per_gpu_utilization"],
          result["migrations"])

`sim.multiclient.run_multiclient` is a thin shim over this engine (with
``n_gpus``/``affinity`` kwargs; the defaults reproduce the single-GPU PR-1
runs bit-for-bit), and `benchmarks/serving_scale.py` drives it with
`StubSession`s to measure sustained sessions per GPU at large client counts.

Flight recorder (`serving.obs`): pass ``tracer=obs.Tracer()`` to the engine
(or ``run_multiclient``, or ``examples/multi_client.py --trace out.json``)
and every grant, migration, labeling launch, preemption cut, fused
train→select→encode stage and per-client uplink/downlink transfer lands as
a span in **simulated** time. ``tracer.dump("out.json")`` writes
deterministic Chrome trace-event JSON — open it at https://ui.perfetto.dev
("Open trace file"; processes are the server, each ``gpu<g>`` with
``stream:label``/``stream:train``/``grants`` threads, and each
``client<i>``; counter tracks carry queue depth, labeling backlog and
per-stream utilization). The engine's results dict is assembled from
`obs.MetricsRegistry`, an ``observability`` section reports the
modeled-vs-measured cost audit (`obs.drift_report` over `core.timing`
stage stats), and `obs.debug_snapshot` unifies the fused-path cache /
counter introspection hooks. Tracing defaults off and the recorder never
changes the schedule: two runs, traced or not, pop identical events.

Fault model (`serving.faults`): chaos is a *plan*, not a dice roll.
``ServingConfig(faults=FaultPlan(...))`` injects a seeded, fully
deterministic fault schedule — per-transfer link loss (splitmix64-hashed
draws, one counter per direction per client), link outage windows
(`OutageWindow`, up/down/both, fleet-wide or per-client), cyclic
`network.RateTrace` bandwidth replay (`LinkSpec.from_trace` loads the
``benchmarks/traces/*.json`` fixtures), device crash windows
(`CrashWindow`) and thermal slowdowns (`SlowdownWindow`). Frame uploads
retry with exponential backoff plus deterministic jitter and are abandoned
(frames dropped, bytes accounted) after ``max_retries``; delta downloads
use *supersede* semantics — a lost delta is retransmitted only while it is
still the newest one, otherwise the retransmit slot notes a ``supersede``
and the client waits for the fresh delta already in flight, inferring on
its stale model meanwhile (``chaos.final_staleness_max_s`` gauges the
damage). A device crash kills the in-flight grant; the ``gpu_done``
watchdog (armed per grant generation) recovers it — releases the device,
spills residency so survivors restage from scratch, and requeues every
member session — while admission control sheds new requests only when the
whole pool is dead. ``FaultPlan.none()`` (the default) is bit-identical to
PR-7: no extra events, no RNG draws, byte-identical traces. The reference
chaos gate lives in ``benchmarks/serving_scale.py --smoke --chaos`` /
``scripts/ci.sh --chaos``.

Fleet control plane (`serving.fleet`): at 10^4-10^5 clients the per-object
path drowns in Python — one heap entry, one dict lookup, one bound-method
call per client per tick. ``FleetState(n, ...)`` stores the whole stub
fleet as struct-of-arrays numpy columns and the engine, handed one, switches
to *cohort events*: clients sharing a timestamp ride a single heap entry
(`Event.client` becomes an index array, ``Event.n`` its multiplicity) and
each event kind is handled by one vectorized batch handler. Policies grow an
array-native ``rank(t, clients=..., ...) -> argsort`` beside the per-object
``pick``, and admission prices unique parameter rows once and parks by a
single argsort+cumsum. The contract is **bit-identical results**: same
events_processed, same mIoU/latency floats, byte-identical flight-recorder
traces under ``FaultPlan.none()`` — anything the vector path cannot
reproduce exactly (tracing, chaos, per-link traces) silently drops to the
scalar lane per cohort. ``telemetry="moments"`` (also on `StubSession`)
folds per-sample lists into running (count, sum, max) so memory stays O(n)
at 10^5 clients; means then agree to ~1 ulp rather than bit-for-bit. The
gate lives in ``benchmarks/serving_scale.py --smoke --fleet`` /
``scripts/ci.sh --fleet``; the ``fleet`` section of BENCH_serving.json
records the 10^3 -> 10^5 sweep (events/sec, RSS) and the measured
fleet-vs-per-object throughput ratio at 10^4.

Sharded execution (`launch.host_mesh` + `core.batched`): the pool's
modeled per-device parallelism can run on *real* jax devices.
``GPUPool(device_backend="jax")`` binds every modeled `GPUDevice` to a
concrete ``jax.Device`` (one live device per slot; a wider pool raises;
``"modeled"``, the default, keeps ``jax_device=None`` and is
bit-identical), and ``core.batched.train_phases_sharded`` executes
co-resident groups on distinct devices as one multi-device step — either
per-device async dispatch (byte-identical to the serial fused path) or a
single ``shard_map`` along the session axis (``spmd=True``, fp16 wire
deltas within 1 ULP). Force an N-device mesh in a CPU container with
``REPRO_HOST_DEVICES=N source scripts/env.sh`` (the flag must be set
before jax initializes — `launch.host_mesh.host_devices` explains when it
is too late). Per-device measured-vs-modeled seconds surface in
``obs.drift_report()[...]["per_device"]``; the gate lives in
``benchmarks/serving_scale.py --smoke --sharded`` /
``scripts/ci.sh --sharded``.
"""
from repro.serving.engine import ServingConfig, ServingEngine
from repro.serving.events import Event, EventQueue
from repro.serving.faults import (
    CrashWindow,
    FaultInjector,
    FaultPlan,
    OutageWindow,
    SlowdownWindow,
)
from repro.serving.fleet import FleetSessionView, FleetState
from repro.serving.network import ClientNetwork, Link, LinkSpec, RateTrace
from repro.serving.obs import (
    MetricsRegistry,
    Tracer,
    debug_snapshot,
    drift_report,
    validate_trace,
)
from repro.serving.policies import (
    POLICIES,
    AffinityAware,
    Assignment,
    EarliestDeadlineFirst,
    FairRoundRobin,
    GainAware,
    GPURequest,
    SchedulingPolicy,
    make_policy,
)
from repro.serving.resources import (
    GPUDevice,
    GPUPool,
    MigrationModel,
    StreamModel,
)
from repro.serving.session import (
    SegServingSession,
    SessionBase,
    StubSession,
    train_many,
)

__all__ = [
    "Event", "EventQueue", "ClientNetwork", "Link", "LinkSpec",
    "SchedulingPolicy", "FairRoundRobin", "EarliestDeadlineFirst",
    "GainAware", "AffinityAware", "Assignment", "GPURequest", "POLICIES",
    "make_policy", "GPUDevice", "GPUPool", "MigrationModel", "StreamModel",
    "SegServingSession", "SessionBase", "StubSession", "train_many",
    "ServingConfig", "ServingEngine",
    "Tracer", "MetricsRegistry", "debug_snapshot", "drift_report",
    "validate_trace",
    "FaultPlan", "FaultInjector", "OutageWindow", "CrashWindow",
    "SlowdownWindow", "RateTrace",
    "FleetState", "FleetSessionView",
]
