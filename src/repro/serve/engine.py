"""InferenceEngine: continuous cross-request batching over the fused
round timeline.

``PrivateModel.__call__`` serves one caller; under concurrent traffic that
means every request pays its own full round count — N requests pay the
*sum* of their rounds.  The engine redesigns the serving surface around
the round-fused protocol instead: callers ``submit(tenant, x)`` into an
admission queue and get a future back; a schedule-driven ``BatchPolicy``
forms micro-batches from the queue; each micro-batch executes every
request as one sibling stream of ONE plan replay, so all requests advance
through the protocol in lockstep and the batch pays **max-over-requests
rounds** per ReLU call (``core.schedule.simulate_merged`` is the exact
prediction, validated against the ``CoalescingComm`` counters).

The execution contract (tested property-style in ``tests/test_engine.py``):

- **Bit-exactness**: with the default policy, batched execution of any
  request mix is bit-identical — share level, not just reveal level — to
  serial per-request execution on the same shares/triples.  Each request
  keeps its own protocol key stream (forked as
  ``Session.request_key(request_id)``, so admission order is irrelevant)
  and its own triples (from its tenant's metered provider); coalescing
  only changes the wire layout, never a value.
  ``BatchPolicy(merge_identical=True)`` additionally merges identical
  (n_elements, k, m) streams into one protocol stream per round
  (``relu_many`` auto-batching: fewer payloads and kernel passes, bytes
  can only drop) — each ReLU's *revealed* values are unchanged, but the
  output share splits differ, so downstream fixed-point truncation may
  wobble the last bit versus serial execution; it is opt-in for that
  reason.
- **Rounds**: measured fused rounds of a batch equal
  ``simulate_merged``'s prediction exactly, and — since every request
  replays the same network — equal max-over-requests rounds, not the sum.
- **Tenancy**: every tenant owns a ``beaver.MeteredProvider``; triple
  consumption is attributed per tenant and an element budget turns
  over-quota submissions into failed futures instead of half-run batches.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Callable, Deque, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro import errors
from repro.core import beaver, comm as comm_lib, ring, schedule as schedule_lib
from repro.core.mpc_tensor import MPCTensor
from repro.api.compile import PrivateModel, compile as compile_model
from repro.api.plan import LAN, NETWORKS, NetworkPreset, Plan, trace_plan
from repro.api.session import Session
from repro.runtime.spans import span
from repro.runtime.watchdog import StragglerWatchdog


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


@dataclasses.dataclass(frozen=True)
class BatchPolicy:
    """When does a micro-batch stop admitting and start running?

    The policy is driven by ``core.schedule`` predictions, not heuristics
    on queue length: a batch admits the next queued request while the
    predicted fused-round latency *per request* of the merged group set
    keeps improving (merging is nearly free in rounds — every request
    replays the same network — so admission normally pays only the extra
    wire bytes), and closes when

    - the relative per-request latency gain of admitting the next request
      drops to ``min_gain`` or below ("stops improving"),
    - ``max_batch`` requests are admitted, or
    - the head request has waited ``max_wait_s`` (the deadline; checked by
      ``InferenceEngine.poll`` — ``flush`` drains unconditionally).

    ``network`` prices the timeline (LAN default; under WAN the byte term
    matters and large batches genuinely stop improving).
    ``merge_identical`` opts into cross-request ``relu_many``
    auto-batching (see the module docstring for the bit-exactness
    tradeoff).  ``bucket`` controls plan/lowering-cache shape bucketing:
    ``"exact"`` (default — one cache entry per distinct request shape,
    bit-exact) or ``"pow2"`` (batch dim padded up to the next power of
    two with zero shares: fewer cache entries and recompiles, outputs
    sliced back; the bit-exactness oracle is then serial execution of the
    *padded* request).
    """

    network: Union[NetworkPreset, str] = LAN
    max_batch: int = 8
    max_wait_s: float = float("inf")
    min_gain: float = 0.0
    merge_identical: bool = False
    bucket: str = "exact"

    @property
    def preset(self) -> NetworkPreset:
        return (NETWORKS[self.network] if isinstance(self.network, str)
                else self.network)

    def bucket_shape(self, shape: Sequence[int]) -> Tuple[int, ...]:
        shape = tuple(int(s) for s in shape)
        if self.bucket == "exact":
            return shape
        if self.bucket == "pow2":
            return (_next_pow2(shape[0]),) + shape[1:]
        raise ValueError(f"unknown bucket mode {self.bucket!r} "
                         "(expected 'exact' or 'pow2')")


@dataclasses.dataclass(frozen=True)
class Request:
    """One admitted request: who asked, what they sent, when."""

    id: int
    tenant: str
    x: MPCTensor                       # possibly padded to the shape bucket
    key: jax.Array                     # protocol key = request_key(id)
    arrival_s: float
    shape: Tuple[int, ...]             # bucketed execution shape
    out_batch: int                     # caller's true batch (pre-padding)
    deadline_s: Optional[float] = None  # completion budget from arrival


class RequestFuture:
    """Handle for a submitted request.  ``result()`` drains the engine's
    queue if the request has not run yet, then returns the output
    MPCTensor (or raises the stored error, e.g. a tenant's
    ``TripleBudgetExceeded``)."""

    def __init__(self, engine: "InferenceEngine", request: Request):
        self._engine = engine
        self.request = request
        self._value: Optional[MPCTensor] = None
        self._exc: Optional[BaseException] = None
        self._done = False
        self._event = threading.Event()
        self.report: Optional["BatchReport"] = None

    @property
    def done(self) -> bool:
        return self._done

    def result(self, timeout_s: Optional[float] = None) -> MPCTensor:
        """The output shares, draining the engine if needed.

        With ``timeout_s=None`` (historical behaviour) the engine is
        flushed once — every queued batch runs to completion.  With a
        timeout, the engine is *polled* instead (batching policy and
        ``max_wait_s`` deadlines respected) until the request resolves or
        the timeout expires, and an unresolved request raises
        ``errors.ResultTimeout`` instead of spinning forever on a wedged
        engine.

        When the engine's background pump is running (``start_pump``),
        ``result`` never drives execution itself — it just waits on the
        pump (``submit()`` alone makes progress; ``poll``/``flush`` stay
        available as manual overrides).
        """
        if not self._done:
            if self._engine.pump_running:
                if not self._event.wait(timeout_s):
                    raise errors.attach_request(
                        errors.ResultTimeout(
                            f"request {self.request.id} unresolved after "
                            f"{timeout_s}s (pump running, engine queue: "
                            f"{self._engine.pending} pending)"),
                        self.request.id, self.request.tenant)
            elif timeout_s is None:
                self._engine.flush()
            else:
                deadline = time.monotonic() + timeout_s
                while not self._done:
                    self._engine.poll()
                    if self._done:
                        break
                    if time.monotonic() >= deadline:
                        raise errors.attach_request(
                            errors.ResultTimeout(
                                f"request {self.request.id} unresolved "
                                f"after {timeout_s}s (engine queue: "
                                f"{self._engine.pending} pending)"),
                            self.request.id, self.request.tenant)
                    time.sleep(min(0.005, timeout_s / 10.0))
        if self._exc is not None:
            raise self._exc
        if not self._done:
            raise RuntimeError(
                f"request {self.request.id} did not execute: it is no "
                "longer queued but was never resolved (a batch it belonged "
                "to failed — see the engine's earlier error)")
        return self._value

    def _resolve(self, value: MPCTensor, report: "BatchReport") -> None:
        self._value, self.report, self._done = value, report, True
        self._event.set()

    def _fail(self, exc: BaseException) -> None:
        # stamp the originating request's identity, first failure wins (a
        # batch-wide exception is shared by every future it failed)
        if getattr(exc, "request_id", None) is None:
            errors.attach_request(exc, self.request.id, self.request.tenant)
        self._exc, self._done = exc, True
        self._event.set()


@dataclasses.dataclass(frozen=True)
class BatchReport:
    """What one executed micro-batch did vs what the schedule predicted."""

    request_ids: Tuple[int, ...]
    tenants: Tuple[str, ...]
    shapes: Tuple[Tuple[int, ...], ...]
    measured_rounds: int
    measured_bytes: int
    predicted_rounds: int             # simulate_merged over the group set
    predicted_bytes: int
    serial_rounds: int                # sum of per-request rounds (unfused)
    predicted_latency_s: float        # merged timeline under policy.network
    waits_s: Tuple[float, ...]        # per-request queue wait at execution
    retries: int = 0                  # batch re-executions on comm faults
    faults_recovered: int = 0         # transport rounds healed by re-send
    shed: int = 0                     # requests deadline-shed at admission
    execute_s: float = 0.0            # measured wall time of the replay

    @property
    def n_requests(self) -> int:
        return len(self.request_ids)

    @property
    def rounds_saved_ratio(self) -> float:
        """Serial-to-fused round ratio: N identical requests approach N."""
        return self.serial_rounds / max(1, self.measured_rounds)

    def queue_s(self, request_id: int) -> float:
        """Request ``request_id``'s wait in the queue before execution."""
        return self.waits_s[self.request_ids.index(request_id)]


class InferenceEngine:
    """Request-level private-inference serving over one compiled model.

    Example::

        engine = serve.InferenceEngine(afn, params, cfg, plan,
                                       api.Session(key=0),
                                       policy=serve.BatchPolicy(max_batch=4))
        f1 = engine.submit("alice", X1)
        f2 = engine.submit("bob", X2)          # different shape: still one
        f3 = engine.submit("alice", X3)        # micro-batch, rounds shared
        y1 = f1.result().reveal()              # drains the queue
        print(engine.reports[-1].rounds_saved_ratio)

    ``plan`` supplies the HummingBird (k, m) assignment and adder mode;
    per-shape plans for other request shapes are traced on demand into a
    cache keyed by ``(config, hb, bucketed shape)``.  ``tenant_budgets``
    maps tenant names to DReLU-element triple budgets
    (``beaver.MeteredProvider``); unknown tenants default to
    ``default_budget`` (None = unmetered cap).  ``provider_factory`` lets
    deployments hand each tenant its own triple source (default: inline
    sim triples).
    """

    def __init__(self, apply_fn, params, cfg, plan: Plan,
                 session: Optional[Session] = None, *,
                 policy: Optional[BatchPolicy] = None,
                 mpc_forward: Optional[Callable] = None,
                 provider_factory: Optional[Callable[[str], object]] = None,
                 tenant_budgets: Optional[Dict[str, int]] = None,
                 default_budget: Optional[int] = None,
                 report_history: int = 1024,
                 max_batch_retries: int = 2,
                 on_party_crash: Optional[Callable] = None,
                 on_straggler: Optional[Callable] = None,
                 straggler_factor: float = 3.0):
        self.policy = policy if policy is not None else BatchPolicy()
        self.session = session if session is not None else Session(key=0)
        self.model: PrivateModel = compile_model(
            apply_fn, params, cfg, plan, self.session,
            mpc_forward=mpc_forward,
            auto_batch=self.policy.merge_identical)
        self.plan = plan
        self.comm = (self.session.comm
                     if isinstance(self.session.comm, comm_lib.CoalescingComm)
                     else comm_lib.CoalescingComm(self.session.comm))
        self._provider_factory = provider_factory or (
            lambda tenant: beaver.InlineTTP())
        self._tenant_budgets = dict(tenant_budgets or {})
        self._default_budget = default_budget
        self._tenants: Dict[str, beaver.MeteredProvider] = {}
        self._plan_cache: Dict[Tuple, Plan] = {}
        if (plan.calls and plan.input_shape
                and self.policy.bucket_shape(plan.input_shape)
                == tuple(plan.input_shape)):
            # seed only when the traced shape IS its own bucket — under
            # pow2 bucketing a plan traced at batch 3 must not stand in
            # for the padded batch-4 replay it would be cached under
            self._plan_cache[self._cache_key(plan.input_shape)] = plan
        self._queue: Deque[Request] = collections.deque()
        #: pending futures only — resolved ones are popped so a
        #: long-running engine never pins consumed requests' tensors
        self._futures: Dict[int, RequestFuture] = {}
        self._used_ids: set = set()
        self._next_id = 0
        #: a bounded window of recent batches (stats() percentiles read
        #: this; the counters below are lifetime totals)
        self.reports: Deque[BatchReport] = collections.deque(
            maxlen=report_history)
        self._totals = {"requests": 0, "batches": 0, "fused_rounds": 0,
                        "serial_rounds": 0, "retries": 0, "shed": 0,
                        "faults_recovered": 0}
        #: resilience: a retryable comm fault (ResilientComm's retry
        #: budget exhausted on a transient) re-executes the whole batch —
        #: same request keys, providers rolled back, so the retried
        #: results are bit-identical and tenants are billed once.  A
        #: PartyCrashed batch retries only if ``on_party_crash`` revives
        #: the transport (e.g. FaultInjectingComm.restart).
        self.max_batch_retries = max_batch_retries
        self.on_party_crash = on_party_crash
        #: transport hooks (see ``repro.transport.engine_link``): a
        #: two-process deployment replaces each batch attempt's execution
        #: tensors (ship the peer's input shares, keep own rows) and
        #: recombines the peer's output shares after the replay.  None =
        #: single-process execution, unchanged.
        self.on_batch_attempt: Optional[Callable] = None
        self.on_batch_outputs: Optional[Callable] = None
        #: one lock serialises every queue/execution entry point so the
        #: background pump, a frontend's submit threads, and direct
        #: poll/flush callers compose; RLock because poll -> _execute ->
        #: tenant_provider nest.
        self._lock = threading.RLock()
        self._pump_thread: Optional[threading.Thread] = None
        self._pump_stop = threading.Event()
        self.last_pump_error: Optional[BaseException] = None
        #: slow-round detection: each executed batch's per-fused-round
        #: wall time feeds the shared EWMA watchdog (same implementation
        #: as the training loop's per-step straggler detector)
        self.watchdog = StragglerWatchdog(factor=straggler_factor)
        self._on_straggler = on_straggler

    # -- plan / lowering cache -------------------------------------------------
    def _cache_key(self, shape: Sequence[int]) -> Tuple:
        return (type(self.model.cfg).__name__, getattr(self.model.cfg, "name",
                                                       ""),
                self.plan.hb, self.plan.cone,
                self.policy.bucket_shape(shape))

    def plan_for_shape(self, shape: Sequence[int]) -> Plan:
        """The (cached) traced plan replayed for requests of ``shape`` —
        keyed by ``(config, hb, bucketed shape)``, traced on demand via
        ``jax.eval_shape`` (the model is never executed)."""
        key = self._cache_key(shape)
        with self._lock:           # RLock: callers already under it re-enter
            if key not in self._plan_cache:
                if self.model.apply_fn is None:
                    raise errors.ShapeMismatch(
                        f"request shape {tuple(shape)} has no traced plan "
                        "and the engine was built without apply_fn — submit "
                        f"only shape {self.plan.input_shape} or compile "
                        "with the plaintext forward")
                bucket = self.policy.bucket_shape(shape)
                self._plan_cache[key] = trace_plan(
                    self.model.apply_fn, self.model.params, bucket,
                    hb=self.plan.hb, cone=self.plan.cone,
                    name=f"{self.plan.name}@{'x'.join(map(str, bucket))}")
            return self._plan_cache[key]

    @property
    def plan_cache_size(self) -> int:
        with self._lock:
            return len(self._plan_cache)

    # -- tenancy ---------------------------------------------------------------
    def tenant_provider(self, tenant: str) -> beaver.MeteredProvider:
        with self._lock:
            if tenant not in self._tenants:
                self._tenants[tenant] = beaver.MeteredProvider(
                    self._provider_factory(tenant),
                    budget_elements=self._tenant_budgets.get(
                        tenant, self._default_budget))
            return self._tenants[tenant]

    def tenant_usage(self, tenant: str) -> Dict[str, Optional[int]]:
        p = self.tenant_provider(tenant)
        return {"consumed_elements": p.consumed_elements,
                "consumed_bundles": p.consumed_bundles,
                "budget_elements": p.budget_elements,
                "remaining_elements": p.remaining_elements}

    @staticmethod
    def _required_elements(plan: Plan) -> int:
        return sum(n for n, w in plan.triple_specs() if n and w)

    # -- admission -------------------------------------------------------------
    def submit(self, tenant: str, x, *, request_id: Optional[int] = None,
               arrival_s: Optional[float] = None,
               deadline_s: Optional[float] = None) -> RequestFuture:
        """Enqueue one request; returns its future.

        ``x`` is the caller's secret-shared ``MPCTensor`` (a plain array is
        accepted for convenience and secret-shared with a key derived from
        the request key).  ``request_id`` defaults to an auto-increment;
        pass an explicit id to make the request's protocol randomness
        independent of submission order (``Session.request_key``).
        The work after the engine lock is taken runs in an
        ``engine.submit`` host span.

        ``deadline_s`` is a completion budget measured from arrival: at
        execution time a request whose schedule-predicted latency alone
        (a provable lower bound — running it solo cannot be slower than
        that) already overruns the remaining budget is *shed* — its
        future fails with ``errors.DeadlineExceeded`` before a single
        protocol round or triple is spent on it.

        The request's plan is resolved here (traced into the cache if the
        shape is new), so an unservable shape fails the *submit* call —
        batch formation only ever sees cache hits and can never drop
        already-queued requests on a trace error.
        """
        with self._lock, span("engine.submit") as sp:
            if request_id is None:
                request_id = self._next_id
            sp.set_metadata(request_id=request_id)
            if request_id in self._used_ids:
                raise errors.DuplicateRequest(
                    f"request id {request_id} already submitted")
            self.plan_for_shape(x.shape)
            self._used_ids.add(request_id)
            self._next_id = max(self._next_id, request_id + 1)
            key = self.session.request_key(request_id)
            if not isinstance(x, MPCTensor):
                enc_key, key = jax.random.split(key)
                x = MPCTensor.from_plain(enc_key, jnp.asarray(x))
            out_batch = int(x.shape[0])
            bucket = self.policy.bucket_shape(x.shape)
            if bucket != tuple(x.shape):
                pad = bucket[0] - out_batch

                def _pad(a):
                    widths = [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2)
                    return jnp.pad(a, widths)

                x = MPCTensor(ring.Ring64(_pad(x.data.lo), _pad(x.data.hi)),
                              x.frac_bits)
            req = Request(id=request_id, tenant=tenant, x=x, key=key,
                          arrival_s=(time.monotonic() if arrival_s is None
                                     else float(arrival_s)),
                          shape=bucket, out_batch=out_batch,
                          deadline_s=(None if deadline_s is None
                                      else float(deadline_s)))
            fut = RequestFuture(self, req)
            self._futures[request_id] = fut
            self._queue.append(req)
            return fut

    @property
    def pending(self) -> int:
        with self._lock:
            return len(self._queue)

    # -- batching policy evaluation -------------------------------------------
    def _merged_schedule(self, requests: Sequence[Request]):
        """Exact fused timeline of executing ``requests`` as one batch:
        ``simulate_merged`` over the ReLU call rows, plus one coalesced
        round per Beaver-open site (LM secret products).  Open sites align
        positionally across requests — one mpc_forward body drives every
        sibling stream — so site i of all requests shares one round with
        summed payloads."""
        plans = [self.plan_for_shape(r.shape) for r in requests]
        sched = schedule_lib.simulate_merged(
            [p.call_specs() for p in plans],
            cone=self.plan.cone, auto_batch=self.policy.merge_identical)
        open_lists = [p.open_specs() for p in plans]
        for i in range(max((len(o) for o in open_lists), default=0)):
            sched = sched + schedule_lib.simulate_open(
                [o[i] for o in open_lists if i < len(o)])
        return sched

    def _merged_latency(self, requests: Sequence[Request]) -> float:
        sched = self._merged_schedule(requests)
        preset = self.policy.preset
        return sched.latency(preset.bandwidth_bps, preset.rtt_s)

    def _form_batch(self) -> List[Request]:
        """Admit from the queue head while the predicted per-request
        latency of the merged set keeps improving by more than
        ``policy.min_gain`` (relative).  The incumbent latency is carried
        forward, so forming a batch of B costs B merged-schedule
        simulations, not B^2."""
        batch = [self._queue.popleft()]
        lat = self._merged_latency(batch)
        while self._queue and len(batch) < self.policy.max_batch:
            n = len(batch)
            lat_new = self._merged_latency(batch + [self._queue[0]])
            if lat <= 0.0:
                # zero-round incumbent (fully-culled plan): merging is
                # free for it, so the candidate rides along
                gain = 1.0
            else:
                gain = 1.0 - (lat_new / (n + 1)) / (lat / n)
            if gain <= self.policy.min_gain:
                break
            batch.append(self._queue.popleft())
            lat = lat_new
        return batch

    # -- execution -------------------------------------------------------------
    def poll(self, now_s: Optional[float] = None) -> List[BatchReport]:
        """Run every batch that is *ready*: the policy closed it with
        requests still queued behind it (more merging would not help), it
        is full, or its head request hit the ``max_wait_s`` deadline.
        Returns the reports of the batches executed."""
        now = time.monotonic() if now_s is None else float(now_s)
        executed = []
        with self._lock:
            while self._queue:
                head_wait = now - self._queue[0].arrival_s
                deadline = head_wait >= self.policy.max_wait_s
                batch = self._form_batch()
                ready = (deadline or len(batch) >= self.policy.max_batch
                         or bool(self._queue))
                if not ready:
                    # put the still-open batch back, wait for more traffic
                    self._queue.extendleft(reversed(batch))
                    break
                report = self._execute(batch, now)
                if report is not None:
                    executed.append(report)
        return executed

    def flush(self) -> List[BatchReport]:
        """Drain the queue unconditionally (deadlines ignored): form
        policy-shaped batches until nothing is pending."""
        executed = []
        with self._lock:
            while self._queue:
                report = self._execute(self._form_batch(), time.monotonic())
                if report is not None:
                    executed.append(report)
        return executed

    def _execute(self, batch: List[Request],
                 now_s: float) -> Optional[BatchReport]:
        # deadline shedding first: a request whose schedule-predicted solo
        # latency already overruns its remaining budget provably cannot
        # finish in time — fail it typed, before reserving any triples
        shed = 0
        survivors: List[Request] = []
        for r in batch:
            if (r.deadline_s is not None
                    and (now_s - r.arrival_s) + self._merged_latency([r])
                    > r.deadline_s):
                self._futures.pop(r.id)._fail(errors.DeadlineExceeded(
                    f"request {r.id} (tenant {r.tenant!r}): "
                    f"{now_s - r.arrival_s:.3f}s already queued and the "
                    f"schedule-predicted replay alone overruns the "
                    f"{r.deadline_s}s deadline — shed before execution"))
                shed += 1
                continue
            survivors.append(r)
        self._totals["shed"] += shed
        # pre-reserve tenant budgets so a mid-protocol budget error can
        # never leave a half-executed batch: over-quota requests fail
        # their futures here and are dropped before any protocol round
        reserved: Dict[str, int] = {}
        admitted: List[Request] = []
        for r in survivors:
            need = self._required_elements(self.plan_for_shape(r.shape))
            provider = self.tenant_provider(r.tenant)
            if provider.budget_elements is not None:
                already = provider.consumed_elements + reserved.get(r.tenant,
                                                                    0)
                if already + need > provider.budget_elements:
                    self._futures.pop(r.id)._fail(beaver.TripleBudgetExceeded(
                        f"tenant {r.tenant!r}: request {r.id} needs {need} "
                        f"DReLU elements but only "
                        f"{provider.budget_elements - already} of "
                        f"{provider.budget_elements} remain"))
                    continue
            reserved[r.tenant] = reserved.get(r.tenant, 0) + need
            admitted.append(r)
        if not admitted:                 # every request over-quota or shed
            return None
        sched = self._merged_schedule(admitted)
        serial_rounds = sum(
            self.plan_for_shape(r.shape).schedule().n_rounds
            for r in admitted)
        providers = [self.tenant_provider(r.tenant) for r in admitted]
        resilient = comm_lib.find_resilient(self.comm)
        attempts = 0
        while True:
            # per ATTEMPT: fresh key iterators (same request keys — the
            # retry draws the identical stream), provider checkpoints so
            # a rolled-back tenant re-draws identical triples and is
            # billed once, and fresh round/byte marks so the report
            # reflects only the successful attempt
            rounds0, bytes0 = self.comm.n_rounds, self.comm.bytes_tx
            recovered0 = resilient.recovered if resilient else 0
            tokens = [(p, p.checkpoint())
                      for p in dict.fromkeys(providers)]
            key_iters = [iter(jax.random.split(r.key, 256))
                         for r in admitted]
            # transport hook: a two-process deployment ships the peer's
            # input shares here (per attempt — a retried batch re-sends
            # its descriptor) and returns this party's execution tensors
            xs = [r.x for r in admitted]
            if self.on_batch_attempt is not None:
                xs = self.on_batch_attempt(admitted)
            t0 = time.monotonic()
            try:
                with span("engine.execute",
                          request_ids=tuple(r.id for r in admitted)):
                    outs = self.model._run_streams(
                        xs, key_iters, providers,
                        self.comm, self.model.params,
                        auto_batch=self.policy.merge_identical)
                break
            except BaseException as e:
                for p, tok in tokens:
                    p.rollback(tok)
                crash = isinstance(e, errors.PartyCrashed)
                retryable = (errors.is_retryable(e)
                             or (crash and self.on_party_crash is not None))
                if not retryable or attempts >= self.max_batch_retries:
                    # a failed replay must not strand its futures: fail
                    # them all so result() surfaces the error instead of
                    # hanging on a request that left the queue but never
                    # produced an output
                    for r in admitted:
                        self._futures.pop(r.id)._fail(e)
                    raise
                if crash:
                    self.on_party_crash(e)      # revive the transport
                attempts += 1
                self._totals["retries"] += 1
        if self.on_batch_outputs is not None:
            # transport hook: collect the peer's output share rows and
            # recombine into full-party tensors so futures reveal
            outs = self.on_batch_outputs(admitted, outs)
        wall = time.monotonic() - t0
        faults_recovered = ((resilient.recovered - recovered0)
                            if resilient else 0)
        self._totals["faults_recovered"] += faults_recovered
        preset = self.policy.preset
        report = BatchReport(
            request_ids=tuple(r.id for r in admitted),
            tenants=tuple(r.tenant for r in admitted),
            shapes=tuple(r.shape for r in admitted),
            measured_rounds=self.comm.n_rounds - rounds0,
            measured_bytes=self.comm.bytes_tx - bytes0,
            predicted_rounds=sched.n_rounds,
            predicted_bytes=sched.bytes_tx,
            serial_rounds=serial_rounds,
            predicted_latency_s=sched.latency(preset.bandwidth_bps,
                                              preset.rtt_s),
            waits_s=tuple(max(0.0, now_s - r.arrival_s) for r in admitted),
            retries=attempts,
            faults_recovered=faults_recovered,
            shed=shed,
            execute_s=wall)
        self.reports.append(report)
        self._totals["requests"] += report.n_requests
        self._totals["batches"] += 1
        self._totals["fused_rounds"] += report.measured_rounds
        self._totals["serial_rounds"] += report.serial_rounds
        if report.measured_rounds:     # slow-round watchdog (shared EWMA)
            self.watchdog.observe(len(self.reports) - 1,
                                  wall / report.measured_rounds,
                                  on_straggler=self._on_straggler)
        for r, out in zip(admitted, outs):
            if r.out_batch != r.shape[0]:      # slice bucket padding back off
                out = MPCTensor(
                    ring.Ring64(out.data.lo[:, :r.out_batch],
                                out.data.hi[:, :r.out_batch]),
                    out.frac_bits)
            self._futures.pop(r.id)._resolve(out, report)
        return report

    # -- background pump -------------------------------------------------------
    @property
    def pump_running(self) -> bool:
        return self._pump_thread is not None and self._pump_thread.is_alive()

    def start_pump(self, interval_s: float = 0.005,
                   max_wait_s: Optional[float] = None) -> None:
        """Drive the engine from a daemon thread so ``submit()`` alone
        makes progress (the async-frontend contract): the pump ``poll``s
        continuously, and once the head request has aged past
        ``max_wait_s`` (default: the policy's ``max_wait_s``, or 50 ms
        when that is unbounded) it ``flush``es so a lone request is never
        stranded waiting for a batch that will not fill.  ``poll`` and
        ``flush`` remain safe to call manually — everything serialises on
        the engine lock.  A batch failure inside the pump fails its
        futures exactly as a caller-driven batch would and is kept in
        ``last_pump_error``; the pump keeps running."""
        if self.pump_running:
            return
        if max_wait_s is None:
            max_wait_s = (self.policy.max_wait_s
                          if self.policy.max_wait_s != float("inf") else 0.05)
        self._pump_stop.clear()
        self._pump_thread = threading.Thread(
            target=self._pump_loop, args=(float(interval_s),
                                          float(max_wait_s)),
            name="engine-pump", daemon=True)
        self._pump_thread.start()

    def stop_pump(self, timeout_s: float = 5.0) -> None:
        """Stop the background pump (pending requests stay queued)."""
        if self._pump_thread is None:
            return
        self._pump_stop.set()
        self._pump_thread.join(timeout_s)
        self._pump_thread = None

    def _pump_loop(self, interval_s: float, max_wait_s: float) -> None:
        while not self._pump_stop.is_set():
            try:
                executed = self.poll()
                if not executed:
                    with self._lock:
                        head = self._queue[0] if self._queue else None
                        age = (time.monotonic() - head.arrival_s
                               if head is not None else -1.0)
                    if head is not None and age >= max_wait_s:
                        self.flush()
            except Exception as e:          # futures already failed, typed
                with self._lock:
                    self.last_pump_error = e
            self._pump_stop.wait(interval_s)

    # -- aggregate stats -------------------------------------------------------
    def stats(self) -> Dict[str, float]:
        """Lifetime totals (fused vs serial rounds over every executed
        batch) plus measured percentiles over the retained
        ``report_history`` window: each request's queue wait, and each
        batch's execute wall time (the replay, host clock)."""
        with self._lock:
            waits = sorted(w for rep in self.reports for w in rep.waits_s)
            execs = sorted(rep.execute_s for rep in self.reports)
            totals = dict(self._totals)

        def pct(values: List[float], p: float) -> float:
            if not values:
                return 0.0
            return values[min(len(values) - 1, int(p * len(values)))]

        return {
            **totals,
            "rounds_saved_ratio": (totals["serial_rounds"]
                                   / max(1, totals["fused_rounds"])),
            "p50_queue_wait_s": pct(waits, 0.50),
            "p95_queue_wait_s": pct(waits, 0.95),
            "p50_execute_s": pct(execs, 0.50),
            "p95_execute_s": pct(execs, 0.95),
            "slow_batches": len(self.watchdog.stragglers),
        }
