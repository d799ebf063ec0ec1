"""compile(): lower (model, Plan, Session) into a callable PrivateModel.

The MPC forward of a model family is registered once
(``register_mpc_forward``) as a function
``forward(params, tensors, cfg, relu_fn, comm) -> tensors`` over sibling
``MPCTensor`` streams; ``compile`` resolves it from the model config's type
and returns a ``PrivateModel`` that replays the Plan: every ReLU call
draws its keys from the Session's PRNG stream and its Beaver triples from
the Session's ``TripleProvider``, and sibling streams share protocol
rounds through ``relu_many`` (one coalesced exchange per round).
"""
from __future__ import annotations

import dataclasses
import math
import os
import time
from typing import Callable, Dict, List, Optional, Sequence, Union

import jax

from repro import errors
from repro.core import beaver, comm as comm_lib, ring
from repro.core.mpc_tensor import MPCTensor, products_many, relu_many
from repro.runtime import loop as loop_lib
from .plan import Plan
from .session import Session

_MPC_FORWARDS: Dict[type, Callable] = {}

# Compiled whole-replay executables, shared across PrivateModel instances:
# the cache key pins the forward, the plan content (digest), the stream /
# params / payload signatures and the XLA options, so two models compiled
# from the same plan reuse one executable (tests and the serving engine
# construct models freely; XLA compilation is the expensive part).
_REPLAY_CACHE: Dict = {}


@dataclasses.dataclass
class _ReplayEntry:
    """One compiled replay: the AOT executable, the trace-time comm whose
    counters hold the measured round timeline, and the trace/compile cost
    split (surfaced in BENCH_relu.json by ``benchmarks/run.py --quick``)."""

    exe: Callable
    comm: "comm_lib.CoalescingComm"
    trace_s: float
    compile_s: float


def replay_cache_stats() -> List[Dict]:
    """Snapshot of every compiled replay built in this process: the
    trace/compile cost split and the fused round count each executable
    carries.  ``benchmarks/run.py --quick`` reports the sum as the
    engine's dispatch-overhead breakdown (trace + XLA compile happen once
    per signature; warm batches pay neither)."""
    return [{"trace_s": e.trace_s, "compile_s": e.compile_s,
             "n_rounds": e.comm.n_rounds} for e in _REPLAY_CACHE.values()]


def _xla_compiler_options() -> Optional[Dict[str, str]]:
    """``HB_XLA_OPT=<0-3>`` caps the XLA backend optimization level for
    the compiled replay (level 0 roughly halves CPU compile time for ~3x
    slower — still bit-identical — execution; useful when compile
    latency dominates, e.g. running the test suite on the scan backend).
    Unset: XLA's default pipeline."""
    lvl = os.environ.get("HB_XLA_OPT", "")
    if lvl in ("0", "1", "2", "3"):
        return {"xla_backend_optimization_level": lvl}
    return None


def register_mpc_forward(cfg_type: type, forward: Callable) -> None:
    """Register the secret-shared forward for a model-config type.

    ``forward(params, tensors, cfg, relu_fn, comm)`` must evaluate the
    model on a list of sibling MPCTensor streams, calling
    ``relu_fn(tensors, group)`` at every ReLU point (the Plan replay hooks
    in there).

    Example::

        def my_forward(params, hs, cfg, relu_fn, comm):
            hs = [h.matmul_public(params["w1"]) for h in hs]
            hs = relu_fn(hs, 0)                     # ReLU group 0
            return [h.matmul_public(params["w2"]) for h in hs]

        register_mpc_forward(MyConfig, my_forward)
        # api.compile(..., cfg=MyConfig(...), ...) now resolves it
    """
    _MPC_FORWARDS[cfg_type] = forward


def resolve_mpc_forward(cfg) -> Callable:
    for klass in type(cfg).__mro__:
        if klass in _MPC_FORWARDS:
            return _MPC_FORWARDS[klass]
    # model modules register on import; pull the zoo in once before failing
    import repro.models  # noqa: F401
    for klass in type(cfg).__mro__:
        if klass in _MPC_FORWARDS:
            return _MPC_FORWARDS[klass]
    raise errors.UnregisteredModel(
        f"no MPC forward registered for {type(cfg).__name__}; call "
        "repro.api.register_mpc_forward or pass mpc_forward= to compile")


def compile(apply_fn, params, cfg, plan: Plan,
            session: Optional[Session] = None, *,
            mpc_forward: Optional[Callable] = None,
            auto_batch: bool = True) -> "PrivateModel":
    """Bind a model to a Plan and a Session for private inference.

    ``apply_fn(params, x, relu_fn=...)`` is the plaintext forward (kept for
    reference evaluation; may be None).  ``cfg`` is the model config whose
    type resolves the registered MPC forward unless ``mpc_forward`` is
    given explicitly.  ``auto_batch`` controls whether identical sibling
    streams merge into one batched protocol stream per ReLU call (the
    serving default; ``plan.schedule``/``cost``/``estimate`` price
    whichever mode is chosen).

    Example::

        model = api.compile(afn, params, RESNET_SMOKE, plan,
                            api.Session(key=0))
        X = model.encrypt(jax.random.PRNGKey(1), x)
        logits = model(X).reveal()          # private inference
    """
    if mpc_forward is None:
        mpc_forward = resolve_mpc_forward(cfg)
    return PrivateModel(apply_fn=apply_fn, params=params, cfg=cfg, plan=plan,
                        session=session if session is not None else Session(),
                        mpc_forward=mpc_forward, auto_batch=auto_batch)


@dataclasses.dataclass
class PrivateModel:
    """A model compiled for private inference under a Plan + Session.

    ``__call__`` accepts one MPCTensor or a sequence of sibling streams;
    streams share protocol rounds via ``relu_many`` (max-over-streams
    rounds per ReLU layer, one coalesced exchange per round).
    ``serve_step()`` lowers the same replay into a jit-able
    ``step(params, lo, hi, triples, key)`` — mesh-native (one
    collective-permute per fused round) when given a mesh with a party
    axis.

    Example::

        model = api.compile(afn, params, cfg, plan, api.Session(key=0))
        out = model(model.encrypt(key, x))          # one stream
        outs = model([X1, X2, X3])                  # rounds shared 3-way
        print(model.schedule(streams=3).gantt())    # predicted timeline
    """

    apply_fn: Optional[Callable]
    params: object
    cfg: object
    plan: Plan
    session: Session
    mpc_forward: Callable
    auto_batch: bool = True
    _step_cache: Dict = dataclasses.field(default_factory=dict, repr=False,
                                          compare=False)
    _layout_cache: Dict = dataclasses.field(default_factory=dict, repr=False,
                                            compare=False)

    # -- convenience ----------------------------------------------------------
    def encrypt(self, key, x_f) -> MPCTensor:
        """Secret-share a plaintext input."""
        return MPCTensor.from_plain(key, x_f)

    def plaintext(self, x_f, params=None):
        """Reference (non-private) forward, exact ReLU."""
        assert self.apply_fn is not None, "compiled without apply_fn"
        return self.apply_fn(params if params is not None else self.params, x_f)

    def estimate(self, *args, **kwargs) -> float:
        kwargs.setdefault("auto_batch", self.auto_batch)
        return self.plan.estimate(*args, **kwargs)

    def schedule(self, streams: int = 1):
        """Predicted fused-round timeline of one ``__call__`` replay with
        ``streams`` sibling inputs (see ``Plan.schedule``)."""
        return self.plan.schedule(streams=streams, auto_batch=self.auto_batch)

    # -- online phase ---------------------------------------------------------
    def __call__(self, xs: Union[MPCTensor, Sequence[MPCTensor]], *,
                 key=None) -> Union[MPCTensor, List[MPCTensor]]:
        single = isinstance(xs, MPCTensor)
        tensors = [xs] if single else list(xs)
        if key is None:
            key = self.session.next_key()
        outs = self._run(tensors, key, self.session.comm,
                         self.session.provider, self.params)
        return outs[0] if single else outs

    def _run(self, tensors: List[MPCTensor], key, comm, provider, params):
        """Replay the plan over sibling streams: one relu_many per ReLU
        call, keys consumed per stream in call order (bit-identical to the
        historical per-call `.relu` path for a single stream).  One shared
        key stream and one shared triple provider — the single-caller
        contract; the serving engine instead passes per-request streams
        through ``_run_streams``."""
        key_iter = iter(jax.random.split(key, 256 * max(1, len(tensors))))
        return self._run_streams(tensors, [key_iter] * len(tensors),
                                 [provider] * len(tensors), comm, params)

    def _run_streams(self, tensors: List[MPCTensor], key_iters: List,
                     providers: List, comm, params,
                     auto_batch: Optional[bool] = None):
        """Replay the plan with *per-stream* key iterators and triple
        providers (the cross-request serving path: stream i is request i,
        its keys fork from ``Session.request_key(request_id)`` and its
        triples are metered against its tenant).  At every ReLU call,
        stream i draws one key from ``key_iters[i]`` and one bundle from
        ``providers[i]`` — exactly what it would draw running alone, so
        with ``auto_batch=False`` the coalesced batch execution is
        bit-identical (share-level) to serial per-request execution on the
        same shares/triples; sibling streams still share every protocol
        round."""
        hb_layers = self.plan.hb.layers
        cone = self.plan.cone
        if auto_batch is None:
            auto_batch = self.auto_batch
        # compiled round loop: the whole replay is ONE jitted program.
        # Plans with secret-product opens (LM attention/gating) stay on
        # the eager loop: their key draws interleave ReLU calls with
        # per-open draws, an order the pre-drawn payload can't express.
        # Under an outer trace (``jax.jit(serve_step())``) the generator
        # loop traces into the caller's program, compiled whole anyway.
        # The replay's size is public metadata (shapes), not share data.
        traced = any(isinstance(t.data.lo, jax.core.Tracer) for t in tensors)
        compiled = (not traced and loop_lib.compiled_eligible(comm)
                    and not getattr(self.plan, "opens", ())
                    and loop_lib.round_loop_mode(lambda: self._relu_calls(
                        tensors, auto_batch, params)) == "scan")
        if compiled:  # hbcheck: disable=R003
            return self._run_streams_compiled(tensors, key_iters, providers,
                                              comm, params, auto_batch)

        def _relu(hs: List[MPCTensor], g: int) -> List[MPCTensor]:
            hb = hb_layers[g]
            keys = [next(key_iters[i]) for i in range(len(hs))]
            tris = [providers[i].relu_triples(math.prod(h.shape), hb.width,
                                              cone=cone)
                    for i, h in enumerate(hs)]
            outs = list(hs)
            # zero-element streams (empty batch) have nothing to compute
            live = [i for i, h in enumerate(hs) if math.prod(h.shape)]
            if live:
                rets = relu_many([keys[i] for i in live],
                                 [hs[i] for i in live],
                                 comm=comm, hbs=[hb] * len(live),
                                 triples_list=[tris[i] for i in live],
                                 cone=cone, auto_batch=auto_batch)
                for j, i in enumerate(live):
                    outs[i] = rets[j]
            return outs

        # Secret-product hooks (see Plan.opens): stream i draws ONE key per
        # product site — independent of how many sibling streams run — and
        # derives its Beaver triple inline from it, so batched execution
        # stays share-level bit-identical to serial per-request execution.
        # All sibling opens coalesce into one protocol round.
        def _products(kind, xs, ys):
            keys = [next(key_iters[i]) for i in range(len(xs))]
            return products_many([kind] * len(xs), keys, xs, ys, comm=comm)

        _relu.matmul = lambda xs, ys: _products("matmul", xs, ys)
        _relu.mul = lambda xs, ys: _products("mul", xs, ys)

        return self.mpc_forward(params, tensors, self.cfg, _relu, comm)

    # -- compiled round loop --------------------------------------------------
    def _stream_sig(self, tensors: Sequence[MPCTensor], auto_batch: bool):
        return (auto_batch,) + tuple(
            (tuple(t.shape), t.frac_bits) for t in tensors)

    def _relu_layout(self, tensors: Sequence[MPCTensor], auto_batch: bool,
                     params):
        """Per-ReLU-call (group, per-stream element counts) of one replay,
        in call order — recorded from an abstract (``jax.eval_shape``)
        pass of the forward, so the model is never executed.  This is what
        lets the compiled path draw every call's keys and triples *before*
        tracing: the stateful Python providers stay outside the program,
        in exactly the order the eager loop would have consumed them."""
        sig = self._stream_sig(tensors, auto_batch)
        # sig is public metadata (shapes + frac_bits) — not share data
        if sig not in self._layout_cache:  # hbcheck: disable=R003
            records: List = []

            def relu_rec(hs, g):
                records.append((g, tuple(math.prod(h.shape) for h in hs)))
                return hs

            stub = comm_lib.SimComm()
            jax.eval_shape(
                lambda p, ts: self.mpc_forward(p, list(ts), self.cfg,
                                               relu_rec, stub),
                params, tuple(tensors))
            self._layout_cache[sig] = tuple(records)
        return self._layout_cache[sig]

    def _relu_calls(self, tensors: Sequence[MPCTensor], auto_batch: bool,
                    params) -> int:
        """ReLU calls of one replay summed over its live sibling streams:
        the size of the program the compiled round loop would unroll."""
        return sum(sum(1 for n in ns if n)
                   for g, ns in self._relu_layout(tensors, auto_batch, params)
                   if not self.plan.hb.layers[g].is_identity)

    def _plan_digest(self) -> str:
        if "digest" not in self._layout_cache:
            self._layout_cache["digest"] = self.plan.digest()
        return self._layout_cache["digest"]

    def _compiled_replay(self, sig, auto_batch: bool, params, tensors,
                         payload) -> _ReplayEntry:
        """The compiled whole-replay program for one stream signature.

        Keys and Beaver triples enter as program *inputs* (pre-drawn per
        call), never as baked constants; every ReLU layer runs
        ``relu_many(loop="scan")`` on a private ``CoalescingComm`` over
        ``SimComm``, so each fused round is one flipped exchange inside
        the program and the dense adder levels of solo streams collapse
        into ``lax.scan`` (carry buffers donated by XLA's loop
        double-buffering).  The private comm's Python counters fill once,
        at trace time; the entry keeps that comm so every *execution* can
        replay the measured timeline onto the caller's comm.

        AOT ``lower``/``compile`` (rather than plain ``jax.jit``) pins the
        executable to the cache key — everything that could change the
        trace (plan digest, stream/params/payload signatures, XLA
        options) is in the key, so one entry always maps to one trace and
        its counters stay exact — and records the trace-vs-compile cost
        split that ``benchmarks/run.py --quick`` reports.
        """
        opts = _xla_compiler_options()
        abstract = jax.tree_util.tree_map(
            lambda l: (jax.numpy.shape(l), jax.numpy.result_type(l).name),
            (params, payload))
        key = (self.mpc_forward, self._plan_digest(), sig, auto_batch,
               jax.tree_util.tree_structure((params, payload)),
               tuple(jax.tree_util.tree_leaves(abstract)),
               None if opts is None else tuple(sorted(opts.items())))
        if key in _REPLAY_CACHE:
            return _REPLAY_CACHE[key]
        hb_layers = self.plan.hb.layers
        cone = self.plan.cone
        cc = comm_lib.CoalescingComm()

        def replay(params, tensors, payload):
            calls = iter(payload)

            def _relu(hs, g):
                keys, tris = next(calls)
                outs = list(hs)
                live = [i for i, h in enumerate(hs) if math.prod(h.shape)]
                if live:
                    hb = hb_layers[g]
                    rets = relu_many([keys[i] for i in live],
                                     [hs[i] for i in live],
                                     comm=cc, hbs=[hb] * len(live),
                                     triples_list=[tris[i] for i in live],
                                     cone=cone, auto_batch=auto_batch,
                                     loop="scan")
                    for j, i in enumerate(live):
                        outs[i] = rets[j]
                return outs

            return self.mpc_forward(params, list(tensors), self.cfg,
                                    _relu, cc)

        t0 = time.perf_counter()
        lowered = jax.jit(replay).lower(params, tensors, payload)
        t1 = time.perf_counter()
        exe = (lowered.compile() if opts is None
               else lowered.compile(compiler_options=opts))
        t2 = time.perf_counter()
        entry = _ReplayEntry(exe=exe, comm=cc, trace_s=t1 - t0,
                             compile_s=t2 - t1)
        _REPLAY_CACHE[key] = entry
        return entry

    def _run_streams_compiled(self, tensors: List[MPCTensor], key_iters,
                              providers, comm, params, auto_batch: bool):
        """``_run_streams`` on the compiled round-loop backend.

        Same contract, same share-level outputs: stream i draws one key
        from ``key_iters[i]`` and one provider bundle per ReLU call in
        call order (so triple metering, pool positions, and retry
        rollback behave identically to the eager loop), then the cached
        compiled replay executes the entire online phase in one XLA call.
        The caller's ``CoalescingComm`` counters advance by the traced
        round timeline, keeping measured-vs-schedule accounting intact.
        """
        layout = self._relu_layout(tensors, auto_batch, params)
        hb_layers = self.plan.hb.layers
        cone = self.plan.cone
        payload = []
        for g, ns in layout:
            hb = hb_layers[g]
            keys = tuple(next(key_iters[i]) for i in range(len(tensors)))
            tris = tuple(providers[i].relu_triples(ns[i], hb.width, cone=cone)
                         for i in range(len(tensors)))
            payload.append((keys, tris))
        entry = self._compiled_replay(self._stream_sig(tensors, auto_batch),
                                      auto_batch, params, tuple(tensors),
                                      tuple(payload))
        outs = entry.exe(params, tuple(tensors), tuple(payload))
        if isinstance(comm, comm_lib.CoalescingComm):
            comm.replay_counters(entry.comm.n_rounds,
                                 list(entry.comm.round_bytes),
                                 list(entry.comm.round_parts))
        return outs

    def replay_stats(self, tensors: Sequence[MPCTensor],
                     auto_batch: Optional[bool] = None) -> Optional[Dict]:
        """Trace/compile cost split of the compiled replay for this stream
        signature, if one has been built (``benchmarks/run.py --quick``
        reports it as the dispatch-overhead breakdown)."""
        if auto_batch is None:
            auto_batch = self.auto_batch
        sig = self._stream_sig(list(tensors), auto_batch)
        for key, entry in _REPLAY_CACHE.items():
            # sig is public metadata (shapes + frac_bits), not share data
            if key[0] is self.mpc_forward and key[2] == sig:  # hbcheck: disable=R003
                return {"trace_s": entry.trace_s,
                        "compile_s": entry.compile_s,
                        "n_rounds": entry.comm.n_rounds}
        return None

    # -- mesh serving ---------------------------------------------------------
    def serve_step(self, mesh=None, *, party_axis: str = "party",
                   data_axis: Optional[str] = None) -> Callable:
        """step(params, lo, hi, triples, key) -> (lo, hi) logits shares.

        ``lo``/``hi`` are the Ring64 limbs of the input shares, shape
        (2, B, ...); ``triples`` is the offline pool (one bundle or None
        per ReLU call, see ``Plan.triple_specs``), entering as step inputs
        so the TTP material is party-sharded too.

        With ``mesh=None`` (legacy path) the replay runs on the session's
        comm with the party dimension materialised (``SimComm``) and the
        caller's in_shardings *hope* XLA shards each exchange sensibly.

        With a mesh carrying a ``party_axis``, the step is **mesh-native**:
        the fused replay executes inside ``shard_map`` over the party axis
        with ``CoalescingComm`` over a ``MeshComm`` base, so every fused
        protocol round of the whole network lowers to exactly ONE
        ``lax.ppermute`` of one flattened uint32 buffer — the compiled
        HLO's collective-permute census equals ``plan.schedule()``'s
        ``(n_rounds, round_bytes)`` prediction, collective for collective
        (asserted in tests/test_mesh_serving.py via
        ``runtime.hlo_analyzer.collective_census``).  The party axis may
        have size 2 (one device slice per non-colluding server) or size 1
        (``make_mpc_smoke_mesh``; both parties on one shard, exchanges
        stay local).  The mesh path requires an explicit triple pool —
        inline providers would have to conjure cross-party randomness
        inside a single party's shard.

        With ``data_axis``, the step additionally shards the *request
        batch* over that mesh axis (the ROADMAP data-axis item): lo/hi
        split their batch dimension, ``triples`` must be the data-sharded
        pool from ``beaver.shard_pool(pool, mesh.shape[data_axis])`` (each
        leaf carries a leading data-shard dim holding that shard's
        bit-level element slice), and every data shard runs an independent
        party-axis protocol on its batch rows — the per-shard HLO
        collective census is unchanged (same fused rounds, per-shard
        payloads) and the revealed outputs equal the unsharded replay's.

        Example::

            mesh = launch.mesh.make_mpc_mesh()        # (2, n_data)
            step = jax.jit(model.serve_step(mesh))
            lo, hi = step(params, X.data.lo, X.data.hi, pool, key)

            sharded = beaver.shard_pool(pool, mesh.shape["data"])
            step2 = jax.jit(model.serve_step(mesh, data_axis="data"))
            lo, hi = step2(params, X.data.lo, X.data.hi, sharded, key)
        """
        if mesh is None:
            def step(params, lo, hi, triples, key):
                x = MPCTensor(ring.Ring64(lo, hi))
                provider = (beaver.TriplePool(triples) if triples is not None
                            else self.session.provider)
                out = self._run([x], key, self.session.comm, provider,
                                params)[0]
                return out.data.lo, out.data.hi

            return step

        from jax.sharding import PartitionSpec

        if party_axis not in mesh.axis_names:
            raise ValueError(
                f"mesh axes {mesh.axis_names} carry no {party_axis!r} axis")
        if data_axis is not None and data_axis not in mesh.axis_names:
            raise ValueError(
                f"mesh axes {mesh.axis_names} carry no {data_axis!r} axis")
        axis_size = mesh.shape[party_axis]

        def _replay(params, lo, hi, triples, key):
            comm = comm_lib.CoalescingComm(
                comm_lib.MeshComm(party_axis, axis_size))
            if data_axis is not None:
                # sharded pool: strip the (local size 1) data-shard dim
                triples = jax.tree_util.tree_map(lambda a: a[0], triples)
            x = MPCTensor(ring.Ring64(lo, hi))
            out = self._run([x], key, comm, beaver.TriplePool(triples),
                            params)[0]
            return out.data.lo, out.data.hi

        def step(params, lo, hi, triples, key):
            if triples is None:
                raise ValueError(
                    "mesh-native serve_step needs an offline triple pool "
                    "(beaver.gen_plan_triples(key, plan.triple_specs()))")
            share = (PartitionSpec(party_axis, data_axis) if data_axis
                     else PartitionSpec(party_axis))
            rep = PartitionSpec()
            fused = jax.shard_map(
                _replay, mesh=mesh,
                in_specs=(rep, share, share,
                          beaver.pool_party_specs(triples, party_axis,
                                                  data_axis=data_axis), rep),
                out_specs=(share, share), check_vma=False)
            return fused(params, lo, hi, triples, key)

        return step

    def jit_step(self, mesh=None, *, party_axis: str = "party",
                 data_axis: Optional[str] = None) -> Callable:
        """Cached-lowering serve path: ``serve_step`` built once per
        (mesh, party_axis, data_axis) and — on the mesh backend — wrapped
        in ``jax.jit`` so repeated calls reuse the compiled executable
        (jax's own trace cache then keys on the padded batch shape, which
        is why the serving engine buckets request shapes).  The sim path
        is returned unjitted — its triple providers are stateful Python —
        but on the default ``scan`` round-loop backend (``runtime/loop``)
        its inner replay runs through the cached compiled program anyway:
        providers draw outside the program, the online phase is one XLA
        call.
        """
        cache_key = (mesh, party_axis, data_axis)
        if cache_key not in self._step_cache:
            step = self.serve_step(mesh, party_axis=party_axis,
                                   data_axis=data_axis)
            self._step_cache[cache_key] = (
                jax.jit(step) if mesh is not None else step)
        return self._step_cache[cache_key]
