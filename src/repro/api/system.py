"""System + Session: the one front door over every SLED execution backend.

``System.build(spec)`` turns a :class:`~repro.api.spec.ServeSpec` into a
running deployment — it builds the model pair once, constructs the backend
the spec names (lock-step reference loop, in-process ServerEngine, replica
Router, or the asyncio transport runtime), owns warmup and the shared jitted
:class:`~repro.core.engine.VerifySteps` bundle, and hands out sessions:

    spec = ServeSpec(backend="engine", devices=2, max_new=16)
    system = System.build(spec)
    session = system.open_session()
    for ev in session.generate():      # TokenEvent / RoundEvent / DoneEvent
        ...
    session.result                     # unified SessionResult

``system.serve()`` runs the spec's whole default fleet concurrently and
returns a :class:`~repro.api.events.ServeResult` (per-session results plus
merged EngineStats/ClientStats) — that is what launch/serve.py and the
benchmarks drive.  All four backends commit token-identical streams for the
same spec under greedy drafting on lossless links; the cross-backend
equivalence test (tests/test_api.py) and the CI api-smoke job hold that
line.

Sessions on the in-process backends interleave cooperatively: each
``generate()`` pump admits waiting sessions, submits ready drafts, and steps
the engine once, so concurrently-pumped sessions batch together exactly as
the raw driver loops did.  Transport sessions run the real asyncio client
under the hood (a dedicated loop thread when a single session is streamed).
"""

from __future__ import annotations

import asyncio
import dataclasses
import logging
import queue
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, Iterator, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.api.events import (
    DoneEvent,
    Event,
    RoundEvent,
    ServeResult,
    SessionResult,
    TokenEvent,
)
from repro.api.spec import FaultSpec, ServeSpec
from repro.cluster import Router
from repro.configs.base import get_config
from repro.core import engine_loop
from repro.core.engine import EngineStats
from repro.core.server_engine import EdgeDeviceKit, ServerEngine
from repro.models.kvcache import supports_paged_attention
from repro.models.model_zoo import build_model, perturb_params
from repro.quant.quantize import dequantize_pytree, quantize_pytree
from repro.serving.devices import NETS
from repro.transport.client import ClientStats, EdgeClient
from repro.transport.links import make_link
from repro.transport.server import TransportServer

log = logging.getLogger(__name__)

_ENGINE_BACKENDS = ("engine", "cluster", "transport")


# ---------------------------------------------------------------------------
# model construction (shared by every backend)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ModelBundle:
    """The built draft/target pair for one ModelSpec — reusable across
    Systems so a spec sweep pays model init once."""

    target_cfg: Any
    draft_cfg: Any
    target: Any
    draft: Any
    target_params: Any
    draft_params: Any

    @property
    def vocab(self) -> int:
        return self.target_cfg.vocab_size


def model_config(mspec, arch: str, layers: Optional[int], **changes):
    """One side of the spec's model pair as a config: the registered
    ``arch`` at the spec's widths (smoke preset or published), its
    vocabulary overridden only when the spec names one, its depth cut to
    ``layers`` when given."""
    cfg = get_config(arch)
    if mspec.widths == "smoke":
        cfg = cfg.reduced()
    if mspec.vocab_size is not None:
        changes["vocab_size"] = mspec.vocab_size
    if layers is not None:
        changes["num_layers"] = layers
    return dataclasses.replace(cfg, **changes)


def build_models(mspec) -> ModelBundle:
    """Deterministically build the spec's model pair: target from
    ``key(seed)`` (optionally weight-quantized), draft from ``key(seed+1)``
    (optionally noise-perturbed so greedy acceptance is non-trivial)."""
    tcfg = model_config(mspec, mspec.arch, mspec.target_layers)
    dcfg = model_config(mspec, mspec.draft_arch, mspec.draft_layers, name="edge-draft")
    if tcfg.vocab_size != dcfg.vocab_size:
        raise ValueError(
            f"draft {mspec.draft_arch} (vocab {dcfg.vocab_size}) and target "
            f"{mspec.arch} (vocab {tcfg.vocab_size}) must share a vocabulary"
        )
    target, draft = build_model(tcfg), build_model(dcfg)
    kw = {"max_pos": 256} if not tcfg.use_rope else {}
    tp = target.init_params(jax.random.key(mspec.seed), **kw)
    if mspec.bits < 16:
        tp = dequantize_pytree(quantize_pytree(tp, mspec.bits))
    dp = perturb_params(draft.init_params(jax.random.key(mspec.seed + 1)), mspec.draft_noise)
    return ModelBundle(tcfg, dcfg, target, draft, tp, dp)


def build_draft_variant(mspec, *, draft_layers: Optional[int], draft_noise: float):
    """One device class's draft bundle: the spec's draft arch/vocab/seed with
    overridden depth and perturbation noise.  Deterministic — params come
    from ``key(seed+1)`` exactly like :func:`build_models`, so a class whose
    overrides equal the spec model's reproduces ``models.draft_params``
    bit-for-bit (System.build just reuses the shared bundle there)."""
    dcfg = model_config(mspec, mspec.draft_arch, draft_layers, name="edge-draft")
    draft = build_model(dcfg)
    dp = perturb_params(draft.init_params(jax.random.key(mspec.seed + 1)), draft_noise)
    return dcfg, draft, dp


class KitCache:
    """Shared per-class draft weights + jitted drafting kits for spec sweeps.

    Tuner candidates that agree on a class's draft config (arch, layers,
    noise, vocab, seed) reuse the built params; candidates that also agree
    on the kit knobs (k, c_th, greedy, attn_chunk) reuse the compiled
    EdgeDeviceKit — a sweep over fleet candidates pays each distinct draft
    build and device-side compile once instead of once per System."""

    def __init__(self) -> None:
        self.drafts: Dict[tuple, tuple] = {}  # draft key -> (cfg, model, params)
        self.kits: Dict[tuple, EdgeDeviceKit] = {}


# ---------------------------------------------------------------------------
# sessions
# ---------------------------------------------------------------------------


class Session:
    """One device's stream against a System backend.

    ``generate()`` yields typed events (TokenEvent* RoundEvent ... DoneEvent)
    and leaves the unified :class:`SessionResult` in ``.result``; ``run()``
    drains the generator and returns the result directly.
    """

    def __init__(
        self,
        system: "System",
        device_id: int,
        prompt: np.ndarray,
        max_new: int,
        join_tick: int = 0,
    ):
        self._system = system
        self.device_id = device_id
        self.prompt = np.asarray(prompt, np.int32)
        self.max_new = max_new
        self.join_tick = join_tick
        self.result: Optional[SessionResult] = None
        self._events: deque = deque()
        self._sink: Optional[Callable[[Event], None]] = None
        self._device = None  # EdgeDevice once admitted (in-process backends)
        self._last_drafted = 0
        self._rounds = 0
        self._drafted = 0
        self._accepted = 0
        self._fallback_rounds = 0
        self._fallback_tokens = 0
        self._committed = 0
        self._t_open = time.time()
        self._trace: List = []  # per-round TraceEvents (telemetry on)

    @property
    def done(self) -> bool:
        return self.result is not None

    def generate(self) -> Iterator[Event]:
        return self._system._generate(self)

    def run(self) -> SessionResult:
        for _ in self.generate():
            pass
        return self.result

    # -- event plumbing (driven by the System backends) ----------------------

    def _push(self, ev: Event) -> None:
        if self._sink is not None:
            self._sink(ev)
        else:
            self._events.append(ev)

    def _note_round(
        self,
        tokens: np.ndarray,
        n_drafted: int,
        n_accepted: int,
        fallback: bool = False,
    ) -> None:
        toks = [int(t) for t in np.asarray(tokens).reshape(-1)]
        for t in toks:
            if self._committed < self.max_new:
                self._push(TokenEvent(self.device_id, t, self._committed))
            self._committed += 1
        self._push(
            RoundEvent(
                device_id=self.device_id,
                round=self._rounds,
                n_drafted=int(n_drafted),
                n_accepted=int(n_accepted),
                tokens=tuple(toks),
                fallback=fallback,
            )
        )
        self._rounds += 1
        self._drafted += int(n_drafted)
        if fallback:
            self._fallback_rounds += 1
            self._fallback_tokens += len(toks)
        else:
            self._accepted += int(n_accepted)

    def _finish(
        self,
        tokens,
        client: Optional[ClientStats] = None,
        shed: bool = False,
    ) -> None:
        tokens = [int(t) for t in tokens][: self.max_new]
        self.result = SessionResult(
            device_id=self.device_id,
            tokens=tokens,
            rounds=self._rounds,
            drafted=self._drafted,
            accepted=self._accepted,
            fallback_rounds=self._fallback_rounds,
            fallback_tokens=self._fallback_tokens,
            wall_seconds=(
                client.wall_seconds if client is not None else time.time() - self._t_open
            ),
            shed=shed,
            client=client,
            trace=self._trace,
        )
        self._system._waiting.pop(self.device_id, None)
        self._system._running.pop(self.device_id, None)
        self._push(DoneEvent(self.device_id, len(tokens)))


# ---------------------------------------------------------------------------
# the facade
# ---------------------------------------------------------------------------


class System:
    """A built SLED deployment: models + the spec's execution backend."""

    def __init__(
        self,
        spec: ServeSpec,
        models: ModelBundle,
        engine: Union[ServerEngine, Router, None],
        kit: Optional[EdgeDeviceKit],
        class_kits: Optional[List[EdgeDeviceKit]] = None,
    ):
        self.spec = spec
        self.models = models
        self.engine = engine  # ServerEngine | Router | None (reference)
        self.kit = kit
        # fleet backends: one kit per resolved device class (kit_for routes)
        self.class_kits: List[EdgeDeviceKit] = list(class_kits or [])
        self._waiting: Dict[int, Session] = {}
        self._running: Dict[int, Session] = {}
        self._used_ids: set = set()
        self._tick = 0
        self._t0: Optional[float] = None
        self._ref_steps: Optional[dict] = None
        # one transport fleet at a time: the engine below is not thread-safe,
        # and each fleet run owns its own TransportServer + event loop
        self._transport_lock = threading.Lock()

    # -- construction --------------------------------------------------------

    @classmethod
    def build(
        cls,
        spec: ServeSpec,
        *,
        models: Optional[ModelBundle] = None,
        steps=None,
        kit: Optional[EdgeDeviceKit] = None,
        kits: Optional[KitCache] = None,
        warmup: bool = False,
    ) -> "System":
        """Construct the backend the spec names.

        ``models`` / ``steps`` / ``kit`` let spec sweeps share built weights,
        a compiled VerifySteps bundle, and the device-side jitted kit across
        Systems (homogeneous configs only — the engine validates sharing).
        ``kits`` (a :class:`KitCache`) does the same for FLEET sweeps: the
        per-class draft bundles and jitted kits candidates have in common
        are built once and shared across the Systems the sweep constructs.
        """
        spec.validate()
        if spec.telemetry:
            # enable-only: a telemetry spec turns collection on process-wide;
            # it is never flipped back off here, so sweeps that interleave
            # telemetry and plain specs keep collecting (benchmarks that need
            # a clean off-state call telemetry.enable(False) explicitly)
            telemetry.enable(True)
        if models is not None and spec.cluster.has_remote:
            log.warning(
                "a shared ModelBundle cannot ship to remote workers: each "
                "worker rebuilds params from the spec's model seed, so a "
                "bundle that differs from build_models(spec.model) would "
                "break cross-process token identity"
            )
        models = models or build_models(spec.model)
        fam = getattr(models.target_cfg, "family", None)
        if spec.kv_dtype == "int8" and not supports_paged_attention(models.target_cfg):
            # loud, not a warning: a silently-bf16 pool would report double
            # the capacity the deployment actually has
            raise ValueError(
                f"kv_dtype='int8' is not supported for model family {fam!r} "
                f"({spec.model.arch}): its caches ride the gather/scatter "
                "fallback (models/kvcache.py) whose recurrent state leaves "
                "have no quantized layout — serve this family with "
                "kv_dtype='bf16'"
            )
        engine: Union[ServerEngine, Router, None] = None
        if spec.backend in _ENGINE_BACKENDS:
            engine_kw = dict(
                n_slots=spec.slots_per_replica,
                max_len=spec.max_len,
                k_max=spec.k_max,
                policy=spec.scheduler.policy,
                max_wait=spec.scheduler.max_wait,
                straggler_timeout=spec.scheduler.straggler_timeout,
                greedy=spec.greedy,
                attn_chunk=spec.attn_chunk,
                kv_dtype=spec.kv_dtype,
                steps=steps,
            )
            if spec.cluster.has_remote:
                engine = cls._build_remote_cluster(spec, models, engine_kw)
            elif spec.backend == "engine" or (
                spec.backend == "transport"
                and spec.cluster.n_replicas == 1
                and not spec.faults.active
            ):
                # single replica: the bare engine (TransportServer fronts a
                # Router or an engine interchangeably); a fault schedule
                # needs the Router's supervision, so chaos runs keep it
                engine = ServerEngine(models.target, models.target_params, **engine_kw)
            else:  # cluster, or transport fronting a replica set
                n_slots = engine_kw.pop("n_slots")
                engine = Router.build(
                    models.target,
                    models.target_params,
                    replicas=spec.cluster.n_replicas,
                    n_slots=n_slots,
                    placement=cls._placement(spec),
                    migrate_on_retire=spec.cluster.migrate_on_retire,
                    faults=spec.cluster.faults,
                    **engine_kw,
                )
            if spec.faults.active and isinstance(engine, Router):
                from repro.cluster.faults import ChaosInjector

                engine.chaos = ChaosInjector(spec.faults, engine)
        kit = kit or EdgeDeviceKit(
            models.draft,
            models.draft_params,
            k_max=spec.k_max,
            c_th=spec.c_th,
            greedy=spec.greedy,
            attn_chunk=spec.attn_chunk,
        )
        class_kits = cls._build_class_kits(spec, models, kits) if spec.fleet.active else None
        system = cls(spec, models, engine, kit, class_kits=class_kits)
        if warmup:
            system.warmup()
        return system

    @classmethod
    def _placement(cls, spec: ServeSpec):
        """The Router placement argument: the spec's policy name, or a
        ClassAffinityPlacement wired to the fleet's device→class map so
        each device class gets a home replica (drafts of one class share
        verify batches — one k, one draft distribution per batch)."""
        if spec.cluster.placement == "class-affinity" and spec.fleet.active:
            from repro.cluster.router import ClassAffinityPlacement

            ranges = tuple((rc.lo, rc.hi) for rc in spec.resolved_classes())

            def class_index(dev: int, _ranges=ranges) -> int:
                for i, (lo, hi) in enumerate(_ranges):
                    if lo <= dev < hi:
                        return i
                return dev  # late-joined id outside the fleet: own bucket

            return ClassAffinityPlacement(class_index)
        return spec.cluster.placement

    @classmethod
    def _build_class_kits(
        cls, spec: ServeSpec, models: ModelBundle, cache: Optional[KitCache]
    ) -> List[EdgeDeviceKit]:
        """One jitted drafting kit per resolved fleet class.  Classes whose
        draft config matches the spec model ride the shared ModelBundle
        (same params object — no rebuild); distinct configs build their own
        deterministic variant.  Identical (draft, k, c_th) classes share
        one kit — and via ``cache`` so do identical classes across sweep
        candidates — so the device-side scan compiles once per distinct
        shape."""
        mspec = spec.model
        cache = cache if cache is not None else KitCache()
        out: List[EdgeDeviceKit] = []
        for rc in spec.resolved_classes():
            dkey = (mspec.draft_arch, mspec.widths, rc.draft_layers, rc.draft_noise,
                    mspec.vocab_size, mspec.seed)
            if (rc.draft_layers, rc.draft_noise) == (mspec.draft_layers, mspec.draft_noise):
                bundle = (models.draft_cfg, models.draft, models.draft_params)
            else:
                bundle = cache.drafts.get(dkey)
                if bundle is None:
                    bundle = build_draft_variant(
                        mspec, draft_layers=rc.draft_layers, draft_noise=rc.draft_noise
                    )
                    cache.drafts[dkey] = bundle
            _, dmodel, dparams = bundle
            kkey = dkey + (rc.k, rc.c_th, spec.greedy, spec.attn_chunk)
            kit_c = cache.kits.get(kkey)
            if kit_c is None:
                kit_c = EdgeDeviceKit(
                    dmodel, dparams,
                    k_max=rc.k, c_th=rc.c_th,
                    greedy=spec.greedy, attn_chunk=spec.attn_chunk,
                )
                cache.kits[kkey] = kit_c
            out.append(kit_c)
        return out

    @classmethod
    def _build_remote_cluster(cls, spec: ServeSpec, models, engine_kw) -> Router:
        """Assemble a mixed local/remote Router from the spec's replica list.

        Each remote replica either DIALS a worker you already started (the
        ReplicaSpec names an address) or SPAWNS one on a private unix socket
        (no address; the System reaps it on close()).  The worker is then
        PLACED: it receives this spec reduced to one single-replica engine —
        same model seed, same pool shape — and rebuilds params
        deterministically, which is what keeps a cross-process fleet
        token-identical to the in-process cluster.  Local entries construct
        ServerEngines in this process, sharing one compiled bundle."""
        from repro.cluster import RemoteReplica, spawn_worker
        from repro.cluster.faults import FaultyChannel
        from repro.cluster.remote import DEFAULT_TIMEOUT

        policy = spec.cluster.faults
        rpc_timeout = policy.rpc_timeout_s if policy.rpc_timeout_s > 0 else DEFAULT_TIMEOUT
        # drop/delay/flap chaos events act on the control channel, so remote
        # channels get wrapped whenever the schedule contains one
        wrap_channels = any(
            e.kind in ("drop", "delay", "flap") for e in spec.faults.events
        )
        n_slots_default = engine_kw.pop("n_slots")
        steps = engine_kw.pop("steps", None)
        # the chaos schedule is executed by the ROUTER against its replicas;
        # the spec a worker is placed with must not carry it (and 'engine'
        # backend rejects fault schedules outright)
        worker_base = spec.with_backend("engine", faults=FaultSpec())
        replicas: list = []
        try:
            for rs in spec.cluster.replica_specs:
                slots = rs.slots or n_slots_default
                if rs.flavor == "inproc":
                    local = ServerEngine(
                        models.target, models.target_params,
                        n_slots=slots, steps=steps, **engine_kw,
                    )
                    steps = local.steps  # siblings ride the first compile
                    replicas.append(local)
                    continue
                worker_spec = dataclasses.replace(
                    worker_base,
                    scheduler=dataclasses.replace(worker_base.scheduler, slots=slots),
                )
                if rs.address:
                    remote = RemoteReplica.dial(rs.address, timeout=rpc_timeout)
                else:
                    proc, addr = spawn_worker()
                    remote = RemoteReplica.dial(addr, timeout=rpc_timeout)
                    remote.proc = proc
                    remote.spawned = True
                remote.retry_rpcs = policy.retry_rpcs
                if wrap_channels:
                    remote.channel = FaultyChannel(remote.channel)
                remote.place(worker_spec)
                replicas.append(remote)
        except BaseException:
            for r in replicas:
                if getattr(r, "flavor", "local") == "remote":
                    r.drain()
            raise
        return Router(
            replicas,
            placement=cls._placement(spec),
            migrate_on_retire=spec.cluster.migrate_on_retire,
            faults=policy,
        )

    @property
    def steps(self):
        """The jitted VerifySteps bundle (shareable across homogeneous
        Systems); None for the reference backend and for a fleet whose
        first replica is remote (compiled executables cannot cross
        processes)."""
        if self.engine is None:
            return None
        return self.engine.steps if isinstance(self.engine, ServerEngine) else (
            self.engine.replicas[0].steps
        )

    def close(self) -> None:
        """Release cross-process resources: drain every remote worker (and
        reap the ones this System spawned).  In-process backends are
        no-ops; safe to call twice."""
        if isinstance(self.engine, Router):
            self.engine.drain()

    def __enter__(self) -> "System":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def warmup(self, buckets=None) -> Dict[int, float]:
        """Pre-compile the verify buckets (engine-backed backends only)."""
        if self.engine is None:
            return {}
        return self.engine.warmup(buckets)

    def prompts(self) -> np.ndarray:
        """The spec's default workload: ``(devices, prompt_len)`` prompts."""
        return np.asarray(
            jax.random.randint(
                jax.random.key(self.spec.prompt_seed),
                (self.spec.devices, self.spec.prompt_len),
                0,
                self.models.vocab,
            )
        )

    def kit_for(self, device_id: int) -> EdgeDeviceKit:
        """The jitted drafting kit serving ``device_id`` — its device
        class's kit under a fleet spec, else the homogeneous spec kit."""
        if self.class_kits:
            rc = self.spec.class_of(device_id)
            if rc is not None:
                return self.class_kits[rc.index]
        return self.kit

    def rate_for(self, device_id: int) -> Optional[float]:
        """Draft-rate throttle for ``device_id`` in tokens/s (None means
        unthrottled): the class's measured hardware rate scaled by
        ``fleet.rate_scale`` when the fleet emulates device speeds, else
        the transport-level ``draft_rate``."""
        fleet = self.spec.fleet
        if fleet.active and fleet.emulate_rates:
            rc = self.spec.class_of(device_id)
            if rc is not None:
                return rc.hardware_rate() * fleet.rate_scale
        return self.spec.transport.draft_rate

    # -- sessions ------------------------------------------------------------

    def open_session(
        self,
        prompt=None,
        *,
        device_id: Optional[int] = None,
        max_new: Optional[int] = None,
        join_tick: int = 0,
    ) -> Session:
        """Register a stream; it joins the backend when first pumped."""
        if device_id is None:
            device_id = 0
            while device_id in self._used_ids:
                device_id += 1
        if device_id in self._used_ids:
            raise ValueError(f"device {device_id} already has a session")
        if prompt is None:
            defaults = self.prompts()
            if device_id >= defaults.shape[0]:
                raise ValueError(
                    f"no default prompt for device {device_id} "
                    f"(spec.devices={self.spec.devices}); pass prompt="
                )
            prompt = defaults[device_id]
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        budget = max_new or self.spec.max_new
        if (
            self.engine is not None
            and prompt.shape[0] + budget + self.spec.k_max + 1 > self.spec.max_len
        ):
            raise ValueError(
                f"prompt of {prompt.shape[0]} + max_new {budget} + k_max+1 slack "
                f"exceeds the pool row length max_len={self.spec.max_len}"
            )
        self._used_ids.add(device_id)
        session = Session(
            self,
            device_id,
            prompt,
            budget,
            join_tick=join_tick,
        )
        self._waiting[device_id] = session
        return session

    # -- fleet serve ---------------------------------------------------------

    def serve(
        self,
        prompts=None,
        *,
        max_new: Optional[int] = None,
        on_event: Optional[Callable[[Event], None]] = None,
    ) -> ServeResult:
        """Run the whole fleet (spec workload, or explicit ``prompts``)
        concurrently to completion; the one-call driver behind serve.py and
        the benchmarks.

        A System may serve() repeatedly (the engine and its compiled steps
        stay warm), but engine stats are LIFETIME-cumulative across runs —
        benchmarks that need clean per-run stats build a fresh System sharing
        ``models``/``steps``/``kit`` instead.
        """
        if self._waiting or self._running:
            raise RuntimeError("serve() needs a fresh System (sessions already open)")
        # per-run driver state: clock, stagger ticks, and device-id space —
        # repeated serve() calls reuse ids 0..N-1 (prior streams all retired),
        # so runs are comparable and session seeds stay spec-determined
        self._tick, self._t0 = 0, None
        self._used_ids.clear()
        prompts = self.prompts() if prompts is None else np.asarray(prompts)
        sink = on_event or (lambda ev: None)
        sessions = []
        for i in range(prompts.shape[0]):
            s = self.open_session(
                prompts[i],
                device_id=i if i not in self._used_ids else None,
                max_new=max_new,
                join_tick=i * self.spec.scheduler.stagger_ticks,
            )
            s._sink = sink
            sessions.append(s)
        t0 = time.time()
        clients: Optional[ClientStats] = None
        if self.spec.backend == "reference":
            for _ in self._reference_rounds(sessions):
                pass
            stats = self._reference_stats(sessions, time.time() - t0)
        elif self.spec.backend == "transport":
            with self._transport_lock:
                stats, clients = asyncio.run(self._transport_fleet(sessions))
        else:
            deadline = time.time() + 600.0
            while not all(s.done for s in sessions):
                self._pump_inproc()
                if time.time() > deadline:
                    raise RuntimeError("in-process fleet failed to drain in 600s")
            stats = self.engine.stats(time.time() - (self._t0 or t0))
        payload: Optional[dict] = None
        if telemetry.enabled():
            if self.engine is not None and hasattr(self.engine, "telemetry_payload"):
                payload = self.engine.telemetry_payload()
            else:  # reference backend: registry snapshot, no server flight ring
                payload = {"snapshot": telemetry.registry().snapshot(), "flight": []}
        return ServeResult(
            backend=self.spec.backend,
            sessions=[s.result for s in sessions],
            engine=stats,
            clients=clients,
            wall_seconds=time.time() - t0,
            lost_devices=sorted(getattr(self.engine, "lost_devices", []) or []),
            telemetry=payload,
        )

    # -- single-session streaming --------------------------------------------

    def _generate(self, session: Session) -> Iterator[Event]:
        if session.done:
            yield from ()
            return
        if self.spec.backend == "reference":
            gen = self._reference_rounds([session])
        elif self.spec.backend == "transport":
            yield from self._generate_transport(session)
            return
        else:
            gen = self._pump_driver(session)
        for _ in gen:
            while session._events:
                yield session._events.popleft()
        while session._events:
            yield session._events.popleft()

    def _pump_driver(self, session: Session) -> Iterator[None]:
        deadline = time.time() + 600.0
        while not session.done:
            self._pump_inproc()
            if time.time() > deadline:
                raise RuntimeError(f"session {session.device_id} failed to finish in 600s")
            yield None

    def _generate_transport(self, session: Session) -> Iterator[Event]:
        """Stream one transport session: the asyncio client runs on a
        dedicated loop thread and events cross over a queue.  Concurrent
        transport streams serialize behind the System's transport lock (the
        engine is not thread-safe).  Closing the generator early cancels the
        background run and retires the stream best-effort."""
        q: queue.Queue = queue.Queue()
        session._sink = q.put
        done = object()
        cancelled = threading.Event()
        handle: dict = {}

        def work():
            async def runner():
                handle["loop"] = asyncio.get_running_loop()
                handle["task"] = asyncio.current_task()
                await self._transport_fleet([session])

            with self._transport_lock:
                if cancelled.is_set():  # consumer left before our turn
                    q.put(done)
                    return
                try:
                    asyncio.run(runner())
                except asyncio.CancelledError:
                    pass
                except BaseException as e:  # surfaced on the consumer side
                    q.put(e)
                finally:
                    if not session.done:  # cancelled mid-stream: free the slot
                        self._waiting.pop(session.device_id, None)
                        if self.engine is not None and session.device_id in self.engine.streams:
                            self.engine.retire(session.device_id)
                q.put(done)

        t = threading.Thread(target=work, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is done:
                    break
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            cancelled.set()
            if not session.done and handle.get("task") is not None:
                try:
                    handle["loop"].call_soon_threadsafe(handle["task"].cancel)
                except RuntimeError:
                    pass  # loop already closed
            t.join(timeout=60.0)

    # -- in-process backends (engine / cluster) ------------------------------

    def _pump_inproc(self) -> None:
        """One scheduler tick: admit joined sessions, submit ready drafts,
        step the engine, route verdicts back to their sessions.  Pumping from
        several generators interleaves their streams into shared batches."""
        if self.engine is None:
            raise RuntimeError("the reference backend has no engine to pump")
        if self._t0 is None:
            self._t0 = time.time()
        self._tick += 1
        now = time.time() - self._t0
        for dev_id in sorted(self._waiting):
            s = self._waiting[dev_id]
            if s.join_tick > self._tick:
                continue
            if self.engine.admit(dev_id, s.prompt, now) is None:
                break  # pool full: stays waiting, admitted when a slot frees
            s._device = self.kit_for(dev_id).spawn(
                dev_id,
                s.prompt,
                max_len=self.spec.max_len,
                seed=self.spec.session_seed_base + dev_id,
            )
            self._running[dev_id] = s
            del self._waiting[dev_id]
        for s in list(self._running.values()):
            if not s._device.awaiting:
                toks = s._device.draft()
                s._last_drafted = len(toks)
                try:
                    self.engine.submit(s.device_id, toks, time.time() - self._t0)
                except ConnectionError:
                    # the replica died and the stream could not be re-placed;
                    # the shed sweep below turns it into an explicit loss
                    if s.device_id in self.engine.streams:
                        raise
        finished = []
        traced = telemetry.enabled()
        for v in self.engine.step(time.time() - self._t0) or []:
            s = self._running[v.device_id]
            s._device.on_verdict(v)
            if traced:
                s._trace.append(telemetry.TraceEvent(
                    device_id=v.device_id, round=s._rounds,
                    t=time.time() - self._t0, k=s._last_drafted,
                    n_accepted=int(v.n_accepted), n_commit=len(v.tokens),
                    queue_s=float(v.queue_s), verify_s=float(v.verify_s),
                ))
            s._note_round(v.tokens, n_drafted=s._last_drafted, n_accepted=v.n_accepted)
            if len(s._device.committed) >= s.max_new:
                finished.append(s)
        for s in finished:
            self.engine.retire(s.device_id)
            del self._running[s.device_id]
            s._finish(s._device.committed)
        self._sweep_lost()

    def _sweep_lost(self) -> None:
        """Sessions whose streams were shed with an evicted replica end with
        an explicit rejection (SessionResult.shed) carrying whatever was
        committed before the loss — never a hung serve loop."""
        lost = getattr(self.engine, "lost_devices", None)
        if not lost:
            return
        lost = set(lost)
        for dev in [d for d in self._running if d in lost]:
            s = self._running.pop(dev)
            log.warning("session %d was shed with its replica; ending it", dev)
            s._finish(s._device.committed if s._device is not None else [], shed=True)
        for dev in [d for d in self._waiting if d in lost]:
            s = self._waiting.pop(dev)
            s._finish([], shed=True)

    # -- reference backend ---------------------------------------------------

    def _reference_rounds(self, sessions: List[Session]) -> Iterator[None]:
        """Lock-step draft+verify over the sessions' prompts, emitting
        per-round events; yields once per round so single-session streaming
        stays incremental.  A thin consumer of engine_loop.sled_rounds —
        the ONE copy of the ground-truth loop — so the reference backend can
        never drift from sled_generate."""
        spec = self.spec
        lens = {s.prompt.shape[0] for s in sessions}
        if len(lens) > 1:
            raise ValueError(
                "the reference backend batches sessions lock-step and needs "
                f"equal prompt lengths, got {sorted(lens)}"
            )
        prompts = np.stack([s.prompt for s in sessions])
        budgets = [s.max_new for s in sessions]
        committed: List[List[int]] = [[] for _ in sessions]
        gen = engine_loop.sled_rounds(
            self.models.draft, self.models.draft_params,
            self.models.target, self.models.target_params,
            jnp.asarray(prompts),
            max_new=max(budgets),
            k_max=spec.k_max, c_th=spec.c_th, greedy=spec.greedy,
            seed=0, attn_chunk=spec.attn_chunk, steps=self._reference_jits(),
            kv_dtype=spec.kv_dtype,
        )
        for rnd in gen:
            for b, s in enumerate(sessions):
                if len(committed[b]) >= budgets[b]:
                    continue  # this stream is done; it just rides the batch
                row = [int(t) for t in rnd.tokens[b, : int(rnd.n_commit[b])]]
                committed[b].extend(row)
                s._note_round(
                    row, n_drafted=int(rnd.lengths[b]), n_accepted=int(rnd.n_accepted[b])
                )
                if len(committed[b]) >= budgets[b]:
                    s._finish(committed[b])
            if all(s.done for s in sessions):
                break  # heterogeneous budgets: don't ride out the longest row
            yield None

    def _reference_jits(self) -> dict:
        if self._ref_steps is None:
            spec = self.spec
            self._ref_steps = engine_loop.make_sled_steps(
                self.models.draft, self.models.target,
                k_max=spec.k_max, c_th=spec.c_th, greedy=spec.greedy,
                attn_chunk=spec.attn_chunk,
            )
        return self._ref_steps

    def _reference_stats(self, sessions: List[Session], wall: float) -> EngineStats:
        """SimResult-shaped record for the reference loop (no server)."""
        total = sum(len(s.result.tokens) for s in sessions)
        rounds = max((s.result.rounds for s in sessions), default=0)
        drafted = sum(s.result.drafted for s in sessions)
        accepted = sum(s.result.accepted for s in sessions)
        wall = max(wall, 1e-9)
        return EngineStats(
            wstgr=total / wall,
            per_device_rate=total / max(len(sessions), 1) / wall,
            server_busy_frac=1.0,
            rounds=rounds,
            timeouts=0,
            fallback_tokens=0,
            mean_batch_fill=float(len(sessions)),
            mean_round_latency=0.0,
            server_rounds_per_s=rounds / wall,
            streams_served=len(sessions),
            acceptance_rate=accepted / max(drafted, 1),
        )

    # -- transport backend ---------------------------------------------------

    async def _transport_fleet(self, sessions: List[Session]):
        spec, tspec = self.spec, self.spec.transport
        server = TransportServer(self.engine)

        def net_for(dev: int) -> str:
            rc = spec.class_of(dev)
            return rc.net if rc is not None else tspec.net

        def relink(dev: int):
            # mid-stream reconnect hook: a fresh link of the same flavor
            # (and the device's class net), attached to the server before
            # the client re-Hellos on it
            async def dial():
                fresh = make_link(
                    tspec.link,
                    net=NETS[net_for(dev)],
                    seed=spec.session_seed_base + dev,
                )
                server.attach(fresh.server)
                return fresh.device

            return dial

        runs = []
        for idx, s in enumerate(sessions):
            link = make_link(
                tspec.link,
                net=NETS[net_for(s.device_id)],
                seed=spec.session_seed_base + s.device_id,
            )
            server.attach(link.server)
            client = EdgeClient(
                self.kit_for(s.device_id),
                s.device_id,
                s.prompt,
                link.device,
                max_new=s.max_new,
                max_len=spec.max_len,
                qmode=tspec.qmode,
                pipeline=tspec.pipeline,
                verify_timeout=tspec.verify_timeout,
                admit_timeout=tspec.verify_timeout,
                draft_rate=self.rate_for(s.device_id),
                kctl=spec.kctl,
                cctl=spec.cctl,
                seed=spec.session_seed_base + s.device_id,
                on_round=s._note_round,
                reconnect=relink(s.device_id),
            )
            runs.append((idx, s, client))

        async def run_one(idx: int, s: Session, client: EdgeClient):
            await asyncio.sleep(idx * tspec.stagger_s)
            tokens = await client.run()
            s._trace = client.trace  # client-side attribution incl. wire_s
            s._finish(tokens, client=client.stats)

        await asyncio.gather(*(run_one(i, s, c) for i, s, c in runs))
        for _ in range(500):  # let in-flight Close frames retire their streams
            if not self.engine.streams:
                break
            await asyncio.sleep(0.01)
        stats = server.stats()
        await server.stop()
        fleet = ClientStats.merge([c.stats for _, _, c in runs])
        return stats, fleet
