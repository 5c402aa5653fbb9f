"""Engine protocol + registry: one contract, N simulation backends.

Every backend consumes the same inputs (an application exposing
``n_processes`` / ``topology()`` / fragments or a batched step, a
:class:`~repro.runtime.simulator.SimConfig`, an optional
:class:`~repro.runtime.faults.FaultModel`) and produces the same
:class:`~repro.runtime.simulator.SimResult`, so experiment families,
benchmarks, and tests are backend-agnostic.

Each backend registers an :class:`EngineSpec` declaring its capability
surface — which duct layouts it understands, which window schedulers it
offers, whether it shards over a device mesh — so callers (the CLI, the
conformance suite in ``tests/test_engine_conformance.py``) can enumerate
and validate options *before* any JAX tracing starts: a bad combination
fails with one actionable ``ValueError``, never a shape error from inside
a ``shard_map``.

Registered backends:

  event   ``runtime/simulator.py`` — discrete-event heap loop; exact event
          ordering, the reference semantics (DESIGN.md §1)
  jax     ``runtime/engine_jax.py`` — vectorized windowed-time engine; the
          whole population advances per lockstep window as flat JAX arrays,
          with ``jax.vmap`` over seeds for multi-replicate sweeps
          (DESIGN.md §7).  With ``shards`` > 1 the population is
          partitioned into contiguous blocks over a 1-D device mesh
          (``runtime/engine_sharded.py``, DESIGN.md §8); only boundary-edge
          duct traffic crosses shards.  Both variants compose the shared
          window-phase core (``runtime/window_core.py``, DESIGN.md §11)

Orthogonal strategy axes (DESIGN.md §11):

  layout     ``auto`` / ``edge`` / ``dense`` — how duct rings are laid out
             in memory (resolved per topology by ``plan_layout``)
  scheduler  ``auto`` / ``window`` / ``superstep`` / ``pipelined`` — when
             cross-shard boundary exchanges run: every lockstep window,
             batched every ``superstep_windows`` windows (self-paced
             supersteps, DESIGN.md §9), or batched *and* overlapped with
             the next superstep's interior windows via double-buffered
             shadow staging (DESIGN.md §12; sharded engine only)

The jax backend additionally offers ``run_replicates(seeds)``; engines that
lack a native batched form fall back to sequential runs via
:func:`run_replicates`.

Callers select strategies with one frozen
:class:`~repro.runtime.config.RunConfig` value
(``make_engine(RunConfig(engine="jax", layout="dense", shards=8), app,
cfg)``); the legacy loose-kwargs spelling survives behind a deprecation
shim (:func:`_resolve_run`).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import (Callable, Dict, List, Optional, Protocol, Sequence,
                    Tuple, Union, runtime_checkable)

from repro.runtime import spans
from repro.runtime.config import STRATEGY_KEYS, RunConfig
from repro.runtime.faults import FaultModel
from repro.runtime.simulator import SimConfig, SimResult, Simulator

#: window schedulers an engine may declare (EngineSpec.schedulers)
SCHEDULERS: Tuple[str, ...] = ("window", "superstep", "pipelined")
#: duct layouts an engine may declare (EngineSpec.layouts); resolution
#: against a concrete topology lives in ``topologies.plan_layout``
LAYOUTS: Tuple[str, ...] = ("edge", "dense")


@runtime_checkable
class Engine(Protocol):
    """What every simulation backend must provide."""

    name: str

    def run(self) -> SimResult:
        """Execute the configured run and return the QoS result."""
        ...


@dataclasses.dataclass(frozen=True)
class EngineSpec:
    """A registered backend plus its declared capability surface.

    The registry — not the factory — rejects unsupported combinations, so
    every mis-configuration surfaces as one actionable ``ValueError`` with
    the registered vocabulary in the message.  The conformance suite
    iterates :func:`engine_specs` to build its parity matrix, so a newly
    registered engine is conformance-tested by construction.
    """

    name: str
    factory: Callable[..., Engine]
    description: str
    #: duct layouts the backend accepts (beyond the implicit "auto")
    layouts: Tuple[str, ...] = ()
    #: window schedulers the backend offers; "window" = per-window
    schedulers: Tuple[str, ...] = ("window",)
    #: accepts shards > 1 (mesh-sharded dispatch)
    shardable: bool = False
    #: vectorized windowed-time semantics (vs exact event ordering)
    vectorized: bool = False

    def __post_init__(self):
        bad = set(self.layouts) - set(LAYOUTS)
        if bad:
            raise ValueError(
                f"engine {self.name!r} declares unknown layouts {sorted(bad)}; "
                f"known: {LAYOUTS}")
        bad = set(self.schedulers) - set(SCHEDULERS)
        if bad:
            raise ValueError(
                f"engine {self.name!r} declares unknown schedulers "
                f"{sorted(bad)}; known: {SCHEDULERS}")


def _make_event(app, cfg: SimConfig, faults: Optional[FaultModel],
                **kwargs) -> Engine:
    if kwargs:
        raise TypeError(f"unknown engine options {sorted(kwargs)}")
    return Simulator(app, cfg, faults)


def _make_jax(app, cfg: SimConfig, faults: Optional[FaultModel],
              **kwargs) -> Engine:
    shards = kwargs.pop("shards", 1)
    # deferred imports: heavy jax machinery (Pallas and Mosaic among it),
    # a set-up phase of its own
    with spans.span("setup.import"):
        if shards and shards > 1:
            from repro.runtime.engine_sharded import ShardedJaxEngine as cls
            kwargs["shards"] = shards
        else:
            # the unsharded engine understands window + superstep (the
            # W-fused dense megakernel); _validate already rejected
            # pipelined here
            from repro.runtime.engine_jax import JaxEngine as cls
    return cls(app, cfg, faults, **kwargs)


_REGISTRY: Dict[str, EngineSpec] = {}


def register_engine(spec: EngineSpec) -> EngineSpec:
    """Register (or replace) a backend under ``spec.name``."""
    _REGISTRY[spec.name] = spec
    return spec


def engine_specs() -> Tuple[EngineSpec, ...]:
    """All registered backends, in registration order."""
    return tuple(_REGISTRY.values())


def get_engine_spec(name: str) -> EngineSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown engine {name!r}; choose from {sorted(_REGISTRY)}")


register_engine(EngineSpec(
    name="event",
    factory=_make_event,
    description="discrete-event heap loop; exact event ordering "
                "(the reference semantics, DESIGN.md §1)",
))
register_engine(EngineSpec(
    name="jax",
    factory=_make_jax,
    description="vectorized windowed-time engine over the shared "
                "window-phase core; shards > 1 partitions the population "
                "over a device mesh (DESIGN.md §7/§8/§11)",
    layouts=LAYOUTS,
    schedulers=SCHEDULERS,
    shardable=True,
    vectorized=True,
))

#: backward-compat view: engine name -> factory (tests and callers that
#: only need the names should prefer :func:`engine_specs`)
ENGINES = {name: spec.factory for name, spec in _REGISTRY.items()}


def _validate(spec: EngineSpec, kwargs: dict) -> dict:
    """Resolve strategy kwargs against ``spec``; mutates a copy of kwargs.

    Understands the three orthogonal axes — ``shards`` (partitioning),
    ``layout`` (duct memory layout), ``scheduler`` + ``superstep_windows``
    (exchange cadence) — and raises one actionable error per bad
    combination.  Remaining kwargs pass through to the factory untouched.
    """
    kwargs = dict(kwargs)
    shards = kwargs.get("shards", 1) or 1
    superstep = kwargs.get("superstep_windows", 1) or 1
    layout = kwargs.get("layout", "auto")
    scheduler = kwargs.pop("scheduler", "auto")

    if shards > 1 and not spec.shardable:
        raise ValueError(
            f"the {spec.name} engine is single-device; --shards requires a "
            "shardable engine (--engine jax)")
    if layout != "auto" and layout not in spec.layouts:
        if not spec.layouts:
            raise ValueError(
                f"--layout selects the vectorized engines' duct layout "
                f"(DESIGN.md §10); the {spec.name} engine has none — use "
                "--engine jax")
        raise ValueError(
            f"unknown layout {layout!r} for engine {spec.name!r}; choose "
            f"from {('auto',) + spec.layouts}")

    if scheduler == "auto":
        scheduler = "superstep" if superstep > 1 else "window"
    if scheduler not in SCHEDULERS:
        raise ValueError(
            f"unknown scheduler {scheduler!r}; choose from "
            f"{('auto',) + SCHEDULERS}")
    if scheduler not in spec.schedulers:
        raise ValueError(
            f"the {spec.name} engine has no {scheduler!r} scheduler "
            f"(offers: {spec.schedulers}); --superstep-windows requires "
            "--engine jax" if scheduler == "superstep" else
            f"the {spec.name} engine has no {scheduler!r} scheduler "
            f"(offers: {spec.schedulers})")
    if scheduler == "superstep":
        if superstep <= 1:
            raise ValueError(
                "scheduler='superstep' fuses W windows per exchange "
                "(sharded: one collective per superstep; unsharded: one "
                "ring commit per superstep); pass superstep_windows > 1 "
                "(--superstep-windows W) to choose W")
        if shards <= 1 and layout == "edge":
            raise ValueError(
                "the unsharded superstep scheduler is the W-fused dense "
                "megakernel (DESIGN.md §13) and needs the dense layout; "
                "drop --layout edge or pass shards > 1 (--shards)")
    elif scheduler == "pipelined":
        if superstep <= 1:
            raise ValueError(
                "scheduler='pipelined' overlaps superstep k's boundary "
                "exchange with superstep k+1's interior windows; pass "
                "superstep_windows > 1 (--superstep-windows W) to choose W")
        if shards <= 1:
            raise ValueError(
                "scheduler='pipelined' double-buffers the cross-shard "
                "boundary exchange and needs the sharded engine; pass "
                "shards > 1 (--shards)")
    elif superstep > 1:
        raise ValueError(
            "scheduler='window' exchanges every lockstep window, but "
            f"superstep_windows={superstep} was given; drop it or pass "
            "scheduler='superstep'")

    # the event factory takes no strategy kwargs at all; strip the
    # defaults we resolved so TypeError stays reserved for true unknowns
    if not spec.vectorized:
        for key in ("shards", "superstep_windows", "layout"):
            kwargs.pop(key, None)
    else:
        # the resolved scheduler travels to the factory (the sharded
        # engine dispatches its boundary-window strategy on it)
        kwargs["scheduler"] = scheduler
    return kwargs


def _resolve_run(run: Union[RunConfig, str], kwargs: dict) -> Tuple[str, dict]:
    """Normalize the two calling conventions to (engine name, kwargs).

    The preferred form passes a :class:`~repro.runtime.config.RunConfig`
    first — one frozen value carrying every strategy axis.  The legacy
    form (an engine-name string plus loose ``layout=`` / ``scheduler=`` /
    ``shards=`` / ``superstep_windows=`` kwargs) still works through this
    shim, with a :class:`DeprecationWarning` pointing at RunConfig.
    Backend extras (``max_pops``, ``chunk``, ...) pass through either way.
    """
    if isinstance(run, RunConfig):
        clash = sorted(set(kwargs) & set(STRATEGY_KEYS))
        if clash:
            raise TypeError(
                f"strategy kwargs {clash} conflict with the RunConfig; "
                "set them on the RunConfig instead")
        return run.engine, {**run.engine_kwargs(), **kwargs}
    legacy = sorted(set(kwargs) & set(STRATEGY_KEYS))
    if legacy:
        warnings.warn(
            f"passing {legacy} as loose kwargs is deprecated; build a "
            "repro.runtime.config.RunConfig and pass it as the first "
            "argument (make_engine(RunConfig(engine=..., ...), app, cfg))",
            DeprecationWarning, stacklevel=3)
    return run, kwargs


def make_engine(run: Union[RunConfig, str], app, cfg: SimConfig,
                faults: Optional[FaultModel] = None, **kwargs) -> Engine:
    """Build a registered engine from a RunConfig (or a name, legacy).

    The preferred call passes a :class:`~repro.runtime.config.RunConfig`
    carrying the strategy axes — ``engine``, ``layout``
    (``auto``/``dense``/``edge`` duct layout, DESIGN.md §10/§13 — ``auto``
    resolves to the bucketed dense layout on every built-in topology),
    ``scheduler`` (``auto``/``window``/``superstep``/``pipelined`` exchange
    cadence, DESIGN.md §9/§12/§13 — ``auto`` follows
    ``superstep_windows``), ``shards`` (> 1 builds the mesh-sharded
    engine, DESIGN.md §8), and ``superstep_windows`` — validated against
    the engine's :class:`EngineSpec` before the factory runs.  ``kwargs``
    are backend extras such as ``max_pops`` / ``chunk``.  The event engine
    accepts none.

    The legacy form ``make_engine("jax", app, cfg, layout=...)`` routes
    through a deprecation shim; see :func:`_resolve_run`.
    """
    name, kwargs = _resolve_run(run, kwargs)
    spec = get_engine_spec(name)
    kwargs = _validate(spec, kwargs)
    return spec.factory(app, cfg, faults, **kwargs)


def validate_run_config(run: RunConfig) -> None:
    """Eagerly check a RunConfig against its engine's registered spec.

    Entry points (the experiments CLI) call this before any app or JAX
    machinery is built, so a bad combination fails in microseconds with
    the registry's message.
    """
    spec = get_engine_spec(run.engine)
    _validate(spec, run.engine_kwargs())


def run_replicates(run: Union[RunConfig, str], make_app, cfg: SimConfig,
                   seeds: Optional[Sequence[int]] = None,
                   faults: Optional[FaultModel] = None,
                   **engine_kwargs) -> List[SimResult]:
    """Run one replicate per seed, batched where the backend supports it.

    ``make_app(seed)`` builds a fresh application per replicate.  Backends
    exposing a native ``run_replicates`` (the jax engine: one vmapped scan,
    sharded over the device mesh when ``shards`` > 1) get all seeds at
    once; others loop.  ``cfg.seed`` is overridden by each replicate's
    seed.  With a :class:`RunConfig` first argument, ``seeds`` may be
    omitted: the sweep is ``run.seeds(cfg.seed)`` (``replicates`` seeds
    rooted at the SimConfig seed).
    """
    if seeds is None:
        if not isinstance(run, RunConfig):
            raise TypeError("seeds may only be omitted when a RunConfig "
                            "is passed (its replicates field sizes the "
                            "sweep)")
        seeds = run.seeds(cfg.seed)
    eng = make_engine(run, make_app(int(seeds[0])),
                      dataclasses.replace(cfg, seed=int(seeds[0])), faults,
                      **engine_kwargs)
    if hasattr(eng, "run_replicates"):
        return eng.run_replicates([int(s) for s in seeds])
    out = [eng.run()]
    for s in seeds[1:]:
        eng = make_engine(run, make_app(int(s)),
                          dataclasses.replace(cfg, seed=int(s)), faults,
                          **engine_kwargs)
        out.append(eng.run())
    return out
