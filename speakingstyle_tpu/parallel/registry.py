"""The partitioned-program registry: ONE place where XLA compilation happens.

Before this module, four subsystems each re-invented the same compile
ritual — ``jax.jit(fn, donate_argnums=...).lower(*shapes).compile()``
under a donation-warning filter, a compile counter bump, a ProgramCard
mint, per-program gauges, and (sometimes) persistent-compile-cache
wiring: the mesh-sharded train step (training/trainer.py), the serve
lattice (serving/engine.py), the style lattice (serving/style.py), and
the benchmark script of the time. ``ProgramRegistry`` extracts that
ritual behind one guarded entry point:

    (callable, mesh/sharding spec, shape bucket, donation spec)
        -> compiled executable + ProgramCard + compile governance

and jaxlint JL018 makes the guard structural: any ``jax.jit`` reference
or ``.lower().compile()`` chain outside this file is a lint error, so
the zero-steady-state-compiles invariant (JL008's concern) has exactly
one choke point instead of a convention per subsystem.

Governance the registry provides uniformly:

  * **Cache-key semantics** — ``compile()`` keys on (program name, arg
    shape/dtype signature, donation, sharding specs). A repeat request
    returns the SAME ``Compiled`` object without recompiling; the
    registry is the reason "did we already build this program?" has one
    answer instead of four dicts.
  * **Persistent compile cache** — the constructor calls
    ``obs.jaxmon.enable_compilation_cache`` (the one owner of the cache
    directory: ``JAX_COMPILATION_CACHE_DIR``, else ``cache_dir`` — the
    ``train.obs.compilation_cache_dir`` override — else
    ``<checkout>/.jax_cache``) before its first compile, so every
    consumer — serve replicas, style, the trainer — restarts
    warm. Hits/requests land per-registry as
    ``jax_persistent_cache_{hits,requests}_total`` in the registry's
    metrics (the ``watch_compiles`` bus bridge).
  * **Cards with shardings** — every compile mints a ProgramCard
    (obs/cost.py) and stores a JSON-ready row that ALSO records the
    mesh geometry and in/out NamedSharding specs the program was built
    against; ``GET /debug/programs`` serves these rows directly, so a
    mesh replica's programs show how they are partitioned.
  * **Sharded AOT** — ``in_shardings``/``out_shardings`` pass straight
    into ``jax.jit``, which is what lets a serve replica BE a mesh
    slice: the engine compiles every lattice point with its batch axis
    over the mesh's ``data`` axis and outputs replicated for host
    readback (serving/engine.py).

``jit_program`` is the sanctioned constructor for jit-on-first-call
wrappers (the trainer's step functions, the audio DSP decorators): a thin alias of ``jax.jit`` that exists so JL018 can
insist the spelling ``jax.jit`` appears nowhere else in the tree.

Precision is a registry concern too: ``cast_params``/``dequant_params``
are the ONE sanctioned path for converting a weight tree between
serving precisions (``f32``/``bf16``/``int8``) — jaxlint JL025 makes
that structural the same way JL018 does for compiles, so a quantized
program's numerics are auditable in one place. ``compile`` takes a
``precision=`` tag that folds into the cache key and lands on the
ProgramCard row: two programs at the same shape bucket but different
precisions are distinct cache entries, and ``GET /debug/programs``
proves not just WHAT compiled but HOW SMALL.
"""

import contextlib
import functools
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple
from speakingstyle_tpu.obs.locks import make_lock

__all__ = [
    "PRECISIONS",
    "ProgramRegistry",
    "cast_params",
    "dequant_params",
    "jit_program",
    "quiet_donation",
]

# The serving precision axis, widest first. "f32" is the identity tier;
# "bf16" casts float leaves; "int8" stores per-channel symmetric-quantized
# weights that are dequantized to f32 on read inside the compiled program.
PRECISIONS = ("f32", "bf16", "int8")

# Marker keys of the int8 leaf representation: a plain dict holding the
# quantized tensor and its per-channel f32 scale. A dict (not a custom
# pytree node) flows through tree_map / device_put / shardings untouched.
_INT8_KEYS = frozenset(("int8_q", "int8_scale"))


def _is_int8_leaf(x: Any) -> bool:
    return isinstance(x, dict) and set(x.keys()) == set(_INT8_KEYS)


@contextlib.contextmanager
def quiet_donation():
    """CPU (and the int32 length vectors on any backend) cannot always
    honor donation; jax warns per lowering. Donation through the
    registry is best-effort by design — silence exactly that warning."""
    import warnings

    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        yield


def jit_program(fn: Optional[Callable] = None, **jit_kwargs):
    """The sanctioned ``jax.jit`` constructor (usable as a decorator).

    Compile-on-first-call wrappers are legitimate where the shape space
    is unbounded or singular (training steps riding the bucket grid,
    audio DSP over file-length signals); routing their construction
    through the registry module keeps JL018's guarantee meaningful —
    the only file that can spell ``jax.jit`` is this one.
    """
    import jax

    if fn is None:
        return functools.partial(jit_program, **jit_kwargs)
    return jax.jit(fn, **jit_kwargs)


def cast_params(variables: Any, precision: str) -> Any:
    """The sanctioned precision cast: one weight tree in, one serving
    param tree out (jaxlint JL025 forbids spelling this anywhere else).

    * ``"f32"`` — identity (the tree is already the full-precision tier).
    * ``"bf16"`` — every float leaf becomes ``bfloat16``; integer leaves
      (embedding tables' index vectors, step counters) pass through.
    * ``"int8"`` — every float matrix/tensor leaf (ndim >= 2) becomes a
      per-channel symmetric-quantized ``{"int8_q", "int8_scale"}`` pair:
      the scale is ``amax/127`` over all leading axes (one scale per
      output channel, the last axis), weights round-clip into int8, and
      ``dequant_params`` restores f32 on read inside the compiled
      program. Small leaves (biases, LayerNorm vectors, scalars) stay
      f32 — quantizing them saves nothing and costs accuracy.

    Runs on host numpy so param trees can be cast before ``device_put``
    (int8 lives in HBM; dequant happens on-chip at dispatch).
    """
    import jax
    import numpy as np

    if precision not in PRECISIONS:
        raise ValueError(
            f"precision must be one of {PRECISIONS}, got {precision!r}"
        )
    if precision == "f32":
        return variables

    if precision == "bf16":
        import jax.numpy as jnp

        def to_bf16(x):
            arr = np.asarray(x)
            if np.issubdtype(arr.dtype, np.floating):
                return jnp.asarray(arr, jnp.bfloat16)
            return x

        return jax.tree_util.tree_map(to_bf16, variables)

    def to_int8(x):
        arr = np.asarray(x)
        if not np.issubdtype(arr.dtype, np.floating) or arr.ndim < 2:
            return x
        arr = arr.astype(np.float32)
        axes = tuple(range(arr.ndim - 1))
        amax = np.max(np.abs(arr), axis=axes, keepdims=True)
        scale = (amax / 127.0).astype(np.float32)
        scale = np.where(scale == 0.0, np.float32(1.0), scale)
        q = np.clip(np.round(arr / scale), -127, 127).astype(np.int8)
        return {"int8_q": q, "int8_scale": scale}

    return jax.tree_util.tree_map(to_int8, variables)


def dequant_params(variables: Any) -> Any:
    """Restore an ``int8`` param tree to f32 — traceable, so it runs
    INSIDE the compiled program (dequant-on-read: int8 occupies device
    memory, each dispatch widens on-chip). Identity on trees without
    int8 marker leaves, so callers can apply it unconditionally.
    """
    import jax
    import jax.numpy as jnp

    def widen(x):
        if _is_int8_leaf(x):
            return x["int8_q"].astype(jnp.float32) * x["int8_scale"]
        return x

    return jax.tree_util.tree_map(widen, variables, is_leaf=_is_int8_leaf)


def _signature(tree: Any) -> str:
    """Stable hashable shape/dtype signature of an args pytree — the
    shape-bucket component of a program's cache key. Works on
    ShapeDtypeStructs, device/host arrays, and scalars alike."""
    import jax

    def leaf(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return f"{tuple(x.shape)}:{x.dtype}"
        return repr(x)

    return repr(jax.tree_util.tree_map(leaf, tree))


def _sharding_str(sh: Any) -> Optional[str]:
    """Human-readable spelling of a (pytree of) NamedSharding(s) for the
    card table; None passes through (single-device programs)."""
    if sh is None:
        return None
    import jax

    def leaf(s):
        spec = getattr(s, "spec", None)
        return str(spec) if spec is not None else str(s)

    leaves = jax.tree_util.tree_leaves(
        sh, is_leaf=lambda x: hasattr(x, "spec")
    )
    if not leaves:
        return None
    strs = [leaf(s) for s in leaves]
    if len(set(strs)) == 1:
        return strs[0]
    return "(" + ", ".join(strs) + ")"


def _mesh_of(sh: Any) -> Optional[str]:
    """``"2x2"``-style geometry of the first NamedSharding in a spec
    tree (all shardings of one program share the mesh)."""
    if sh is None:
        return None
    import jax

    for s in jax.tree_util.tree_leaves(sh, is_leaf=lambda x: hasattr(x, "spec")):
        mesh = getattr(s, "mesh", None)
        if mesh is not None:
            return "x".join(str(mesh.shape[a]) for a in mesh.axis_names)
    return None


class ProgramRegistry:
    """Compile governance for one consumer (an engine, a style service,
    a trainer run).

    Each registry owns: its program + card tables, a compile counter in
    the consumer's ``MetricsRegistry`` (``counter_name`` keeps the
    historical per-subsystem names — ``serve_compiles_total``,
    ``serve_style_compiles_total`` — working), the backend-compile bus
    subscription (``watch_compiles``), and the persistent-cache hookup.
    Sharing one metrics registry across consumers (the fleet does)
    shares the bus counters; the program tables stay per-registry.
    """

    def __init__(
        self,
        metrics=None,
        *,
        cache_dir: Optional[str] = None,
        counter_name: str = "program_registry_compiles_total",
        prefix: str = "program",
    ):
        from speakingstyle_tpu.obs import MetricsRegistry, watch_compiles
        from speakingstyle_tpu.obs.jaxmon import enable_compilation_cache

        self.metrics = metrics if metrics is not None else MetricsRegistry()
        # backend-compile + persistent-cache events -> this registry's
        # metrics (jax_backend_compiles_total,
        # jax_persistent_cache_{hits,requests}_total)
        watch_compiles(self.metrics)
        self.cache_dir = enable_compilation_cache(cache_dir or "")
        self.prefix = prefix
        self._compiles = self.metrics.counter(
            counter_name,
            help="XLA programs compiled through this ProgramRegistry",
        )
        self._lock = make_lock("ProgramRegistry._lock", kind="rlock")
        self._programs: Dict[Tuple, Any] = {}
        self._by_name: Dict[str, Any] = {}
        self._cards: List[Dict] = []

    # -- introspection ------------------------------------------------------

    @property
    def compile_count(self) -> int:
        return int(self._compiles.value)

    def __len__(self) -> int:
        with self._lock:
            return len(self._programs)

    def get(self, name: str):
        """Latest compiled executable registered under ``name`` (None if
        never compiled) — the lookup consumers key their dispatch tables
        from when they don't hold the executable themselves."""
        with self._lock:
            return self._by_name.get(name)

    def programs(self) -> List[Dict]:
        """The card table: one JSON-ready row per compiled program, in
        compile order, each carrying the ProgramCard cost analysis plus
        the mesh/sharding specs it was built against (the
        ``GET /debug/programs`` payload)."""
        with self._lock:
            return [dict(row) for row in self._cards]

    # -- the single compile entry point -------------------------------------

    def compile(
        self,
        fn: Callable,
        args: Tuple,
        *,
        name: str,
        donate_argnums: Tuple[int, ...] = (),
        static_argnums=None,
        in_shardings=None,
        out_shardings=None,
        compiler_options: Optional[Dict] = None,
        labels: Optional[Dict[str, str]] = None,
        precision: str = "f32",
    ):
        """(callable, sharding spec, shape bucket, donation spec) ->
        compiled executable, with the bookkeeping done.

        ``args`` is the AOT argument tuple — ``jax.ShapeDtypeStruct``s
        or concrete arrays (concrete works because lowering only reads
        shape/dtype/sharding). The cache key is (name, args signature,
        donation, sharding specs): a repeat call returns the stored
        ``Compiled`` without recompiling, so "precompile twice" and
        "two consumers ask for the same bucket" both cost one program.

        ``fn`` may already be a jit wrapper (``jit_program`` output, the
        trainer's case) — it is lowered as-is and the jit construction
        kwargs must then be () / None.

        ``precision`` tags which tier of the precision axis this program
        serves (``f32``/``bf16``/``int8``); it folds into the cache key
        (same bucket, different precision = different program) and onto
        the card row.
        """
        import jax

        if precision not in PRECISIONS:
            raise ValueError(
                f"precision must be one of {PRECISIONS}, got {precision!r}"
            )
        key = (
            name,
            _signature(args),
            repr(donate_argnums),
            repr(static_argnums),
            _sharding_str(in_shardings),
            _sharding_str(out_shardings),
            precision,
        )
        with self._lock:
            exe = self._programs.get(key)
            if exe is not None:
                return exe
            if hasattr(fn, "lower") and not isinstance(fn, type):
                # already a jit wrapper — lower it directly
                jitted = fn
            else:
                kwargs: Dict[str, Any] = {"donate_argnums": donate_argnums}
                if static_argnums is not None:
                    kwargs["static_argnums"] = static_argnums
                if in_shardings is not None:
                    kwargs["in_shardings"] = in_shardings
                if out_shardings is not None:
                    kwargs["out_shardings"] = out_shardings
                jitted = jax.jit(fn, **kwargs)
            t0 = time.monotonic()
            with quiet_donation():
                lowered = jitted.lower(*args)
                exe = (
                    # jaxlint: disable=JL021 reason=the registry lock deliberately serializes all XLA compiles; this is the one sanctioned compile entry point
                    lowered.compile(compiler_options=compiler_options)
                    if compiler_options
                    # jaxlint: disable=JL021 reason=the registry lock deliberately serializes all XLA compiles; this is the one sanctioned compile entry point
                    else lowered.compile()
                )
            self._compiles.inc()
            self._programs[key] = exe
            self._by_name[name] = exe
            self._record(exe, name, donate_argnums, in_shardings,
                         out_shardings, labels, precision,
                         compile_seconds=time.monotonic() - t0)
        return exe

    def _record(self, exe, name, donate, in_sh, out_sh, labels,
                precision="f32", compile_seconds=None) -> None:
        """Mint the ProgramCard, publish gauges, append the card row.
        Caller holds the lock. Card minting only reads compiler metadata
        — it can never itself compile."""
        from speakingstyle_tpu.obs.cost import (
            ProgramCard,
            publish_program_gauges,
        )

        card = ProgramCard.from_compiled(exe, name=name)
        publish_program_gauges(
            self.metrics, card, self.prefix, labels=labels or {}
        )
        row = card.as_dict()
        row["mesh"] = _mesh_of(in_sh) or _mesh_of(out_sh)
        row["in_shardings"] = _sharding_str(in_sh)
        row["out_shardings"] = _sharding_str(out_sh)
        row["donate_argnums"] = list(donate)
        row["precision"] = precision
        # lower + compile wall time (a persistent-cache hit shows as a
        # small number): what a cold start of this program costs
        row["compile_seconds"] = compile_seconds
        if labels:
            row.update({f"label_{k}": v for k, v in labels.items()})
        self._cards.append(row)

    def card(self, name: str) -> Optional[Dict]:
        """The most recent card row registered under ``name``."""
        with self._lock:
            for row in reversed(self._cards):
                if row.get("name") == name:
                    return dict(row)
        return None
