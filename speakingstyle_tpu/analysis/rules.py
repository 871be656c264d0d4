"""AST rule implementations for the jaxlint static analyzer.

Every rule is a function ``(module: ModuleInfo) -> Iterator[Finding]``
registered in ``RULES``. Rules are deliberately heuristic: they resolve
names lexically within one file (no imports, no cross-file types), which
is exactly enough for the hazard classes that destroy TPU throughput —
each is a *syntactic* pattern. Conservative over-reporting is handled by
the committed baseline (tracked-but-allowed) and inline
``# jaxlint: disable=JLxxx`` suppressions, never by weakening a rule to
silence.

Rule catalog (docstrings are the user-facing documentation; the CLI's
``--list-rules`` prints them):

JL001  trace-unsafe Python control flow in traced contexts
JL002  numpy applied to JAX arrays (host fallback / implicit transfer)
JL003  missing donation on state-updating jits; unhashable static args
JL004  host-device sync inside training loops
JL005  recompilation hazards in jitted signatures
JL006  PRNG key reuse without split
JL007  swallowed exceptions (broad except with no handling)
JL008  XLA compilation in hot paths (jit/lower().compile() in loops or
       request handlers; precompile/warmup functions exempt)
JL009  wall-clock time.time() used for duration measurement
       (monotonic-clock rule: durations must use time.monotonic() or
       time.perf_counter(); time.time() is for timestamps only)
JL010  jitted-call timing without a sync: monotonic/perf_counter
       subtraction around a jitted call with no block_until_ready or
       device read in the timed region — async dispatch makes such
       timings measure enqueue cost, not execution
JL011  unbounded queues in serving code: queue.Queue()/LifoQueue()/
       PriorityQueue() with no positive maxsize (or SimpleQueue, which
       cannot be bounded) under speakingstyle_tpu/serving/ — an
       unbounded admission queue makes backpressure meaningless: load
       past capacity accumulates as latency instead of shedding
JL012  unbounded caches in serving code: lru_cache(maxsize=None)/
       functools.cache, or a dict literal/dict() assigned to a
       cache-named target, under speakingstyle_tpu/serving/ — a server
       caching per-request content (styles, mels, ...) grows without
       bound under real traffic; use a bounded LRU with an eviction
       counter (serving/style.py) instead
JL013  unbounded blocking waits in serving code: ``.result()`` or a
       zero-argument ``.get()`` with no ``timeout=`` under
       speakingstyle_tpu/serving/ — a handler or worker parked forever
       on a future/queue survives the very replica failure the
       supervision layer exists to detect; every serving wait needs a
       deadline so a fault resolves as a structured 5xx, not a hang
JL014  hard single-device pinning in training/data code:
       ``device_put(x, jax.devices()[0])`` (or ``jax.local_devices()``,
       directly or via a variable) under training/ or data/ — now that
       the trainer runs on a mesh, placement is a sharding contract;
       a pin to device 0 funnels every batch onto one chip of the mesh
       (correct but 1/N throughput). Pass a NamedSharding instead.
JL015  fresh ndarray allocation in the serving hot path: np.zeros/
       np.full/np.pad/np.concatenate in a dispatch loop or request
       handler under speakingstyle_tpu/serving/ — steady-state serving
       is allocation-free by contract (per-bucket BufferPool leases,
       serving/pool.py); a per-request allocation puts malloc and
       page-zeroing jitter straight into the p999
JL016  bare time.sleep() inside a loop under speakingstyle_tpu/serving/
       — supervision/policy loops (the fleet supervisor, the
       autoscaler) must park on a stop-aware Event.wait(timeout) or
       Condition.wait so close()/drain interrupts them immediately; a
       sleeping thread holds shutdown hostage for up to a full tick
JL017  non-atomic persistent writes under training/ or serving/:
       open(path, "w"/"wb") or np.save/np.savez aimed at a
       checkpoint/artifact-shaped path (ckpt, checkpoint, manifest,
       weights, baseline, snapshot, artifact) with no temp-file +
       os.replace in the enclosing scope — a crash mid-write leaves a
       torn file that reads as CORRUPT, not absent; durable artifacts
       must appear atomically (write <name>.tmp, fsync, os.replace)
JL018  XLA compilation outside the program registry: any reference to
       jax.jit/jax.pjit (call, decorator, functools.partial argument,
       bare attribute), a ``from jax import jit/pjit`` import, or a
       .lower().compile() AOT chain anywhere under speakingstyle_tpu/
       except parallel/registry.py — the registry is the one guarded
       compile entry point (ProgramRegistry.compile for AOT,
       jit_program for jit-on-call wrappers), which is what
       makes the zero-steady-state-compiles invariant structural;
       precompile/warmup fixtures are exempt. Tree baseline: zero.
JL019  full-utterance accumulation in serving code: a list that is
       ``.append``/``.extend``-ed inside a loop and later passed to
       np.concatenate/jnp.concatenate in the same scope, under
       speakingstyle_tpu/serving/ — the accumulate-then-concat shape
       materializes an entire utterance (or chapter) host-side, which
       is exactly what the bounded-memory streaming contract forbids:
       long-form output must flow window-by-window (serving/
       streaming.py) or seam-by-seam (serving/longform.py), never be
       rebuilt whole. Complements JL015 (which flags the concatenate
       CALL in a loop/handler; JL019 catches the concat-after-loop
       spelling JL015's loop test misses). Tree baseline: zero.
JL020  torn-state race: a class attribute accessed under a lock in one
       method and read/written lock-free in another, in a class whose
       methods run on more than one thread (analysis/concurrency.py
       guarded-by inference: ``with self._lock:`` scope tracking plus
       one level of helper call-through, with replica-style local
       receivers bound to the declaring class). Exempt: Events, queue
       objects, obs.registry metrics, the lock objects themselves, and
       ``# jaxlint: disable=JL020 reason=...``. Tree baseline: zero.
JL021  blocking call under a lock (lock convoy / deadlock feeder):
       future.result, Event.wait, queue get/put (SimpleQueue.put is
       non-blocking and exempt), socket send/recv, subprocess, HTTP,
       time.sleep, or a registry/XLA compile while holding any
       recognized lock. Condition.wait on the lock being held is the
       sanctioned wait idiom and exempt. Tree baseline: zero.
JL022  lock-order cycle: nested ``with self._lock`` acquisitions (plus
       self-method and cross-class call-through) form the static
       lock-order graph; a cycle within one module is an error here,
       and the program-wide acyclic order is the checked-in
       analysis/lockorder.json (``cli lockorder --write``), which the
       runtime TrackedLock witness (obs/locks.py) enforces under
       SPEAKINGSTYLE_CHECKS=1. Tree baseline: zero.
JL023  unsupervised thread: ``threading.Thread(...)`` without a
       ``name=`` (invisible to the watchdog/supervision machinery), or
       a thread-creating class with no close()/stop() path that joins
       the thread or sets a stop Event. Scoped to speakingstyle_tpu/
       (test harness threads are deliberately ad hoc).
       Tree baseline: zero.
JL024  unbounded wire call in serving code: an HTTP/socket client
       construct — http.client.HTTPConnection/HTTPSConnection,
       urllib's urlopen, any requests.<verb>/requests.request, or
       socket.create_connection — without an explicit ``timeout``
       under speakingstyle_tpu/serving/. The distributed control
       plane (serving/cluster.py) makes the serving tier a wire
       *client*: dispatches, heartbeats, registration and adoption
       probes all cross host boundaries, and the OS default for a
       connect/read is minutes-to-forever. A single timeout-less call
       re-introduces exactly the unbounded wait JL013 banned for
       futures/queues — a partitioned peer then parks a worker past
       every lease, breaker, and hedge budget. The socket-module
       default (socket.setdefaulttimeout) is process-global state and
       does NOT count: the bound must be visible at the call site.
       Tree baseline: zero.
JL025  out-of-band weight-tree precision cast: ``<tree>.astype(...)``,
       a ``jnp.float32(<tree>)``-style dtype constructor, or a
       ``tree_map(lambda x: x.astype(...), <tree>)`` over a
       params/variables tree anywhere outside the sanctioned
       ``cast_params`` helper in parallel/registry.py. Precision is a
       lattice axis: the registry cache key, ProgramCard rows, and the
       tier canary gates all key on which precision a param tree
       carries, so an inline cast serves weights no gate approved and
       no card records. Tree baseline: zero.
JL026  label-cardinality bomb at a metric registration site:
       per-request identity (req_id, trace_id, span ids, idempotency
       keys, raw text) flowing into a metric NAME or a label VALUE at
       a ``registry.counter/gauge/histogram`` call under
       speakingstyle_tpu/serving/ or obs/ — every distinct label value
       mints a whole new time series, so a per-request label turns a
       bounded /metrics page (and the fleet federation merge over it)
       into an allocation that grows with traffic forever. Per-request
       identity belongs on trace spans and JSONL events; metric labels
       stay bounded (class, replica, reason, bucket).
       Tree baseline: zero.
JL027  audio bytes leaving serving code without the quality choke
       point: an int16 PCM conversion (``.astype(np.int16)``), a RIFF
       container build (``wav_bytes(...)``), or an audio buffer
       serialization (``wav.tobytes()`` — terminal receiver named
       wav/pcm/audio/chunk/piece) in a function with NO
       ``QualityGate.check``/``check_result``/``validate_wav``/
       injected ``quality_check`` call, under speakingstyle_tpu/
       serving/. Every wav must cross obs/quality.py where it is
       produced or served — an unvalidated emission path is invisible
       to the validators, the quality SLO burn stream, and the
       golden-probe degradation drill. Tree baseline: zero.
"""

import ast
import dataclasses
import os
from typing import Dict, Iterator, List, Optional, Set, Tuple

# ---------------------------------------------------------------------------
# shared model
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str
    path: str  # repo-relative posix path
    line: int
    context: str  # enclosing function qualname (or "<module>")
    detail: str  # short, line-number-free (stable across edits)
    message: str  # full human-readable text

    @property
    def fingerprint(self) -> str:
        return f"{self.rule}:{self.path}:{self.context}:{self.detail}"


def _dotted(node: ast.AST) -> str:
    """Best-effort dotted-name of an expression (``jax.random.split``)."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = _dotted(node.value)
        return f"{base}.{node.attr}" if base else node.attr
    if isinstance(node, ast.Call):
        return _dotted(node.func)
    return ""


def _names_in(node: ast.AST) -> Set[str]:
    return {
        n.id for n in ast.walk(node) if isinstance(n, ast.Name)
    }


# calls whose result is a jax array (lexical heuristics)
_ARRAY_PRODUCER_PREFIXES = (
    "jnp.", "jax.numpy.", "jax.nn.", "jax.lax.", "jax.random.",
)
_ARRAY_PRODUCER_SUFFIXES = (".apply", ".init")

# jax transforms whose function argument is traced (jit_program is the
# registry's sanctioned jax.jit alias — parallel/registry.py)
_TRACING_TRANSFORMS = {
    "jax.jit", "jax.grad", "jax.value_and_grad", "jax.vmap", "jax.pmap",
    "jax.checkpoint", "jax.remat", "jit_program",
}

# spellings that construct a jit-on-call wrapper (JL003's call sites)
_JIT_CONSTRUCTORS = {"jax.jit", "jit_program"}

_STATE_PARAM_NAMES = {"state", "variables", "params", "opt_state", "carry"}

_HOST_SYNC_CALLS = {"jax.device_get", "jax.block_until_ready"}

_CONFIG_PARAM_NAMES = {"cfg", "config", "hp", "hparams", "hyper_params"}

_DICTISH_ANNOTATIONS = {"dict", "Dict", "list", "List", "Mapping", "Any"}

_RNG_DERIVERS = {"jax.random.split", "jax.random.fold_in", "jax.random.clone"}


class ModuleInfo:
    """One parsed file plus the pre-analysis every rule shares."""

    def __init__(self, path: str, source: str, tree: ast.Module):
        self.path = path
        self.source = source
        self.tree = tree
        # memoized ast.walk: every rule that used to run its own full
        # traversal shares one cached node list per subtree, so linting a
        # file costs one AST pass (plus one per distinct function subtree
        # a rule inspects) instead of one pass per rule
        self._walk_cache: Dict[int, List[ast.AST]] = {}
        self.parents: Dict[ast.AST, ast.AST] = {}
        for parent in self.walk():
            for child in ast.iter_child_nodes(parent):
                self.parents[child] = parent

        self.functions: List[ast.FunctionDef] = [
            n for n in self.walk()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        self._jitted_names = self._collect_jitted_names()
        self._partial_static_params = self._collect_partial_bindings()
        self._traced = {f for f in self.functions if self._is_traced(f)}

    def walk(self, node: Optional[ast.AST] = None) -> List[ast.AST]:
        """``list(ast.walk(node or tree))``, memoized per subtree. The
        cached list preserves ast.walk's exact BFS order, so findings are
        byte-identical to the per-rule-walk implementation."""
        key = -1 if node is None or node is self.tree else id(node)
        cached = self._walk_cache.get(key)
        if cached is None:
            cached = list(ast.walk(self.tree if key == -1 else node))
            self._walk_cache[key] = cached
        return cached

    # -- context helpers ----------------------------------------------------

    def qualname(self, node: ast.AST) -> str:
        parts: List[str] = []
        cur: Optional[ast.AST] = node
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                                ast.ClassDef)):
                parts.append(cur.name)
            cur = self.parents.get(cur)
        return ".".join(reversed(parts)) or "<module>"

    def enclosing_function(self, node: ast.AST):
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur
            cur = self.parents.get(cur)
        return None

    def enclosing_loops(self, node: ast.AST) -> List[ast.AST]:
        out = []
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.For, ast.While, ast.AsyncFor)):
                out.append(cur)
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                break
            cur = self.parents.get(cur)
        return out

    # -- traced-context detection -------------------------------------------

    def _collect_jitted_names(self) -> Set[str]:
        """Function names that appear as the traced argument of a jax
        transform call anywhere in the file: ``jax.jit(step_fn, ...)``."""
        names: Set[str] = set()
        for node in self.walk():
            if not isinstance(node, ast.Call):
                continue
            callee = _dotted(node.func)
            if callee in _TRACING_TRANSFORMS or (
                callee in ("functools.partial", "partial")
                and node.args
                and _dotted(node.args[0]) in _TRACING_TRANSFORMS
            ):
                for arg in node.args:
                    if isinstance(arg, ast.Name):
                        names.add(arg.id)
        return names

    def _collect_partial_bindings(self) -> Dict[str, Set[str]]:
        """functools.partial(f, kw=..., pos...) binds those params of ``f``
        statically — they are Python values at trace time, not tracers."""
        out: Dict[str, Set[str]] = {}
        defs = {f.name: f for f in self.functions}
        for node in self.walk():
            if not isinstance(node, ast.Call):
                continue
            if _dotted(node.func) not in ("functools.partial", "partial"):
                continue
            if not node.args or not isinstance(node.args[0], ast.Name):
                continue
            fn = defs.get(node.args[0].id)
            if fn is None:
                continue
            bound = out.setdefault(fn.name, set())
            params = [a.arg for a in fn.args.args]
            for i, _ in enumerate(node.args[1:]):
                if i < len(params):
                    bound.add(params[i])
            for kw in node.keywords:
                if kw.arg:
                    bound.add(kw.arg)
        return out

    def _is_traced(self, fn: ast.FunctionDef) -> bool:
        for dec in fn.decorator_list:
            d = _dotted(dec)
            if d in _TRACING_TRANSFORMS or d in ("nn.compact", "nn.remat"):
                return True
            if isinstance(dec, ast.Call):
                dc = _dotted(dec.func)
                if dc in _TRACING_TRANSFORMS:
                    return True
                if dc in ("functools.partial", "partial") and dec.args and \
                        _dotted(dec.args[0]) in _TRACING_TRANSFORMS:
                    return True
        if fn.name in self._jitted_names:
            return True
        # __call__ / compact methods of nn.Module subclasses
        parent = self.parents.get(fn)
        if isinstance(parent, ast.ClassDef):
            bases = {_dotted(b) for b in parent.bases}
            if any(b.endswith("Module") for b in bases):
                if fn.name == "__call__" or any(
                    _dotted(d) == "nn.compact" for d in fn.decorator_list
                ):
                    return True
        return False

    def is_in_traced_context(self, node: ast.AST) -> bool:
        """True if ``node`` sits inside a traced function (nested defs
        inside a traced function execute at trace time too)."""
        cur: Optional[ast.AST] = node
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)) and \
                    cur in self._traced:
                return True
            cur = self.parents.get(cur)
        return False

    # -- per-function dataflow ----------------------------------------------

    def array_locals(self, fn: ast.FunctionDef) -> Set[str]:
        """Names assigned (anywhere in ``fn``) from expressions that produce
        jax arrays: jnp./jax.lax./..., ``.apply(...)``/``.init(...)`` calls,
        or calls of locally-jitted callables."""
        producers: Set[str] = set()
        jitted_locals = set(self._jitted_names)
        # names bound directly to a jit wrapper: g = jax.jit(...)
        for node in self.walk(fn):
            if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
                if _dotted(node.value.func) in _TRACING_TRANSFORMS:
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            jitted_locals.add(t.id)
        # locally @jax.jit-decorated defs
        for sub in self.walk(fn):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)) and \
                    sub in self._traced:
                jitted_locals.add(sub.name)

        def produces_array(value: ast.AST) -> bool:
            if not isinstance(value, ast.Call):
                return False
            callee = _dotted(value.func)
            if callee.startswith(_ARRAY_PRODUCER_PREFIXES):
                return True
            if any(callee.endswith(s) for s in _ARRAY_PRODUCER_SUFFIXES):
                return True
            return callee in jitted_locals

        for node in self.walk(fn):
            if isinstance(node, ast.Assign) and produces_array(node.value):
                for t in node.targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            producers.add(n.id)
        return producers

    def static_params(self, fn: ast.FunctionDef) -> Set[str]:
        """Params known static at trace time: ``self``, partial-bound
        params, and str/int-annotated ones (shape-like by convention)."""
        static = {"self"}
        static |= self._partial_static_params.get(fn.name, set())
        for a in list(fn.args.args) + list(fn.args.kwonlyargs):
            ann = a.annotation
            if ann is not None:
                t = _dotted(ann)
                if isinstance(ann, ast.Subscript):  # Optional[int] etc.
                    t = f"{_dotted(ann.value)}[{_dotted(ann.slice)}]"
                if t in ("str", "int", "Optional[int]", "Optional[str]"):
                    static.add(a.arg)
        return static


# ---------------------------------------------------------------------------
# JL001 — trace-unsafe Python control flow
# ---------------------------------------------------------------------------

_SAFE_CALLS = {
    "isinstance", "len", "hasattr", "getattr", "callable", "issubclass",
    "jnp.issubdtype", "jax.numpy.issubdtype",
}
_SAFE_ATTRS = {"shape", "ndim", "dtype", "size", "sharding"}


def _suspicious_names(test: ast.AST, suspects: Set[str]) -> Set[str]:
    """Bare Name loads from ``suspects`` in ``test``, after pruning
    trace-safe subexpressions (identity checks, metadata attrs, string
    comparisons, isinstance/len)."""

    pruned: Set[ast.AST] = set()

    def prune(node: ast.AST):
        for child in ast.walk(node):
            pruned.add(child)

    for node in ast.walk(test):
        if node in pruned:
            continue
        if isinstance(node, ast.Compare):
            ops_safe = all(isinstance(o, (ast.Is, ast.IsNot)) for o in node.ops)
            str_cmp = any(
                isinstance(c, ast.Constant) and isinstance(c.value, (str, bytes))
                for c in [node.left] + list(node.comparators)
            )
            if ops_safe or str_cmp:
                prune(node)
        elif isinstance(node, ast.Call) and _dotted(node.func) in _SAFE_CALLS:
            prune(node)
        elif isinstance(node, ast.Attribute):
            if node.attr in _SAFE_ATTRS:
                prune(node)
            else:
                # attribute access on a name (cfg.multi_speaker, self.rate)
                # reads config, not array truthiness — prune the VALUE name
                # but keep walking anything deeper than a plain name chain
                if isinstance(node.value, ast.Name):
                    pruned.add(node.value)

    out = set()
    for node in ast.walk(test):
        if node in pruned:
            continue
        if isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            if node.id in suspects:
                out.add(node.id)
    return out


def rule_jl001(mod: ModuleInfo) -> Iterator[Finding]:
    """JL001: Python ``if``/``while``/``assert`` on a potentially traced
    value inside a traced context (@jax.jit functions, functions passed to
    jax transforms, nn.Module ``__call__``/@nn.compact bodies).

    Python branching executes at trace time: on a tracer it raises
    ``TracerBoolConversionError``; on a Python value it silently bakes one
    branch into the compiled program. Parameters of traced functions are
    traced unless marked static (bool flags included — ``donate``/``jit``
    do NOT make bools static), so branch on ``self.*`` config, mark the
    argument static, or use ``jax.lax.cond``/``jnp.where``.
    """
    for fn in mod.functions:
        if fn not in mod._traced:
            continue
        static = mod.static_params(fn)
        params = {
            a.arg
            for a in list(fn.args.args) + list(fn.args.kwonlyargs)
            + ([fn.args.vararg] if fn.args.vararg else [])
            + ([fn.args.kwarg] if fn.args.kwarg else [])
        } - static
        arrays = mod.array_locals(fn)
        suspects = params | arrays
        qual = mod.qualname(fn)
        for node in mod.walk(fn):
            if isinstance(node, (ast.If, ast.While)):
                test = node.test
                kind = "if" if isinstance(node, ast.If) else "while"
            elif isinstance(node, ast.Assert):
                test, kind = node.test, "assert"
            else:
                continue
            hits = _suspicious_names(test, suspects)
            # direct jnp./jax. calls in the test are traced values too
            for call in ast.walk(test):
                if isinstance(call, ast.Call) and _dotted(call.func).startswith(
                    _ARRAY_PRODUCER_PREFIXES
                ):
                    hits.add(_dotted(call.func))
            for name in sorted(hits):
                yield Finding(
                    rule="JL001",
                    path=mod.path,
                    line=node.lineno,
                    context=qual,
                    detail=f"{kind} on {name!r}",
                    message=(
                        f"Python `{kind}` on {name!r} inside traced context "
                        f"{qual}: traced values cannot drive Python control "
                        "flow — use jax.lax.cond/jnp.where, mark the "
                        "argument static, or branch on self.* config."
                    ),
                )


# ---------------------------------------------------------------------------
# JL002 — numpy on jax arrays
# ---------------------------------------------------------------------------


def rule_jl002(mod: ModuleInfo) -> Iterator[Finding]:
    """JL002: ``np.*`` applied to a value produced by jax (jnp/jax.lax/
    jax.random calls, ``.apply``/``.init``, or a jitted callable).

    Inside a traced context this is a host fallback that breaks tracing or
    silently constant-folds; outside, it is an implicit device->host
    transfer (a sync point) that belongs at explicit boundaries only.
    Test files are exempt: round-tripping through numpy is the assertion
    idiom there, and np.testing.* transfers on purpose everywhere.
    """
    p = mod.path.replace("\\", "/")
    if "tests/" in p or os.path.basename(p).startswith("test_"):
        return
    for fn in mod.functions:
        arrays = mod.array_locals(fn)
        if not arrays:
            continue
        qual = mod.qualname(fn)
        traced = mod.is_in_traced_context(fn.body[0]) if fn.body else False
        for node in mod.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            callee = _dotted(node.func)
            if not (callee.startswith("np.") or callee.startswith("numpy.")):
                continue
            if callee.startswith("np.testing") or callee.startswith(
                "numpy.testing"
            ):
                continue  # test assertions transfer on purpose
            used = set()
            for arg in list(node.args) + [k.value for k in node.keywords]:
                used |= _names_in(arg) & arrays
            for name in sorted(used):
                where = (
                    "inside a traced context (host fallback breaks tracing)"
                    if traced
                    else "an implicit device->host transfer (sync point)"
                )
                yield Finding(
                    rule="JL002",
                    path=mod.path,
                    line=node.lineno,
                    context=qual,
                    detail=f"{callee} on {name!r}",
                    message=(
                        f"`{callee}` applied to jax array {name!r} in {qual}: "
                        f"{where}. Use jnp.* on device, or jax.device_get at "
                        "an explicit boundary."
                    ),
                )


# ---------------------------------------------------------------------------
# JL003 — donation / static hashability
# ---------------------------------------------------------------------------


def _jit_callsites(mod: ModuleInfo):
    """Yield (call_node, callee_fndef_or_None, jit_kwargs, decorated_fn).

    Covers ``jax.jit(f, **kw)``/``jit_program(f, **kw)`` calls,
    ``@jax.jit``/``@jit_program`` and
    ``@functools.partial(jax.jit, **kw)`` decorations.
    """
    defs = {f.name: f for f in mod.functions}
    for node in mod.walk():
        if isinstance(node, ast.Call) and \
                _dotted(node.func) in _JIT_CONSTRUCTORS:
            target = None
            if node.args and isinstance(node.args[0], ast.Name):
                target = defs.get(node.args[0].id)
            kwargs = {k.arg for k in node.keywords if k.arg}
            yield node, target, kwargs, None
    for fn in mod.functions:
        for dec in fn.decorator_list:
            if _dotted(dec) in _JIT_CONSTRUCTORS:
                yield dec, fn, set(), fn
            elif isinstance(dec, ast.Call):
                dc = _dotted(dec.func)
                if dc in _JIT_CONSTRUCTORS:
                    yield dec, fn, {k.arg for k in dec.keywords if k.arg}, fn
                elif dc in ("functools.partial", "partial") and dec.args and \
                        _dotted(dec.args[0]) in _JIT_CONSTRUCTORS:
                    yield dec, fn, {k.arg for k in dec.keywords if k.arg}, fn


def _is_state_update_shaped(fn: ast.FunctionDef, state_params: Set[str]) -> bool:
    """Does ``fn`` return an updated copy of a state-like parameter?"""

    updated: Set[str] = set()

    def is_update_expr(expr: ast.AST) -> bool:
        if isinstance(expr, ast.Call):
            callee = _dotted(expr.func)
            head = callee.split(".")[0]
            if callee.endswith(".replace") and head in state_params:
                return True
            if callee in ("optax.apply_updates",):
                return True
            # SomeState(**restored)-style reconstruction mentioning state
            if callee and callee[0].isupper() and "State" in callee:
                return True
        if isinstance(expr, ast.Dict):
            for k, v in zip(expr.keys, expr.values):
                # {**state, ...}: a copied-and-updated state dict
                if k is None and isinstance(v, ast.Name) and \
                        v.id in state_params:
                    return True
        return False

    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and is_update_expr(node.value):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    updated.add(t.id)

    for node in ast.walk(fn):
        if not isinstance(node, ast.Return) or node.value is None:
            continue
        values = (
            list(node.value.elts)
            if isinstance(node.value, ast.Tuple)
            else [node.value]
        )
        for v in values:
            if is_update_expr(v):
                return True
            if isinstance(v, ast.Name) and v.id in updated:
                return True
    return False


def rule_jl003(mod: ModuleInfo) -> Iterator[Finding]:
    """JL003: (a) ``jax.jit`` of a train-step-shaped function (takes a
    state-like argument and returns an updated copy of it) without
    ``donate_argnums``/``donate_argnames`` — without donation every step
    holds two copies of the full state in HBM and pays an extra copy;
    (b) list/dict/set literals passed in ``static_argnums`` positions —
    unhashable statics raise at call time.
    """
    seen: Set[int] = set()
    for node, target, kwargs, _ in _jit_callsites(mod):
        if target is None or id(target) in seen:
            continue
        state_params = {
            a.arg
            for a in target.args.args
            if a.arg in _STATE_PARAM_NAMES or a.arg.endswith("_state")
        }
        if not state_params:
            continue
        if not _is_state_update_shaped(target, state_params):
            continue
        seen.add(id(target))
        if not (kwargs & {"donate_argnums", "donate_argnames"}):
            yield Finding(
                rule="JL003",
                path=mod.path,
                line=node.lineno,
                context=mod.qualname(target),
                detail=f"jit of state-updating {target.name!r} without donation",
                message=(
                    f"jax.jit({target.name}) updates {sorted(state_params)} "
                    "but does not donate it: pass donate_argnums so XLA can "
                    "reuse the input buffers instead of holding two copies "
                    "of the state."
                ),
            )

    # (b) unhashable literals at static positions
    static_of: Dict[str, List[int]] = {}
    for node, target, _, decorated in _jit_callsites(mod):
        call = node if isinstance(node, ast.Call) else None
        if call is None:
            continue
        for k in call.keywords:
            if k.arg == "static_argnums":
                idxs = []
                vals = (
                    k.value.elts
                    if isinstance(k.value, (ast.Tuple, ast.List))
                    else [k.value]
                )
                for v in vals:
                    if isinstance(v, ast.Constant) and isinstance(v.value, int):
                        idxs.append(v.value)
                name = None
                if decorated is not None:
                    name = decorated.name
                else:
                    parent = mod.parents.get(call)
                    if isinstance(parent, ast.Assign):
                        for t in parent.targets:
                            if isinstance(t, ast.Name):
                                name = t.id
                if name and idxs:
                    static_of[name] = idxs
    for node in mod.walk():
        if not isinstance(node, ast.Call) or not isinstance(node.func, ast.Name):
            continue
        idxs = static_of.get(node.func.id)
        if not idxs:
            continue
        for i in idxs:
            if i < len(node.args) and isinstance(
                node.args[i], (ast.List, ast.Dict, ast.Set)
            ):
                kind = type(node.args[i]).__name__.lower()
                yield Finding(
                    rule="JL003",
                    path=mod.path,
                    line=node.lineno,
                    context=mod.qualname(
                        mod.enclosing_function(node) or mod.tree
                    ),
                    detail=f"unhashable {kind} at static arg {i} of "
                           f"{node.func.id!r}",
                    message=(
                        f"call of jitted {node.func.id!r} passes a {kind} "
                        f"literal at static_argnums position {i}: statics "
                        "must be hashable — use a tuple/frozen dataclass."
                    ),
                )


# ---------------------------------------------------------------------------
# JL004 — host sync inside training loops
# ---------------------------------------------------------------------------


def rule_jl004(mod: ModuleInfo) -> Iterator[Finding]:
    """JL004: host-device synchronization inside a loop in ``training/``
    code: ``.item()``, ``float()``/``int()`` on non-constants,
    ``jax.device_get``, ``(jax.)block_until_ready``.

    Each of these drains the dispatch queue: the device goes idle until
    the host catches up, which serializes the step pipeline. Deliberate,
    rate-gated syncs (logging every N steps) belong in the baseline or
    under an inline disable with the gate visible on the same line.
    """
    if "training/" not in mod.path.replace("\\", "/"):
        return
    for node in mod.walk():
        if not isinstance(node, ast.Call):
            continue
        if not mod.enclosing_loops(node):
            continue
        callee = _dotted(node.func)
        detail = None
        if callee in _HOST_SYNC_CALLS:
            detail = callee
        elif isinstance(node.func, ast.Attribute) and node.func.attr in (
            "item", "block_until_ready"
        ):
            detail = f".{node.func.attr}()"
        elif isinstance(node.func, ast.Name) and node.func.id in (
            "float", "int"
        ):
            if node.args and not isinstance(node.args[0], ast.Constant):
                arg_callee = _dotted(node.args[0])
                if not arg_callee.startswith(("time.", "len", "os.")):
                    detail = f"{node.func.id}() on device value"
        if detail is None:
            continue
        fn = mod.enclosing_function(node)
        yield Finding(
            rule="JL004",
            path=mod.path,
            line=node.lineno,
            context=mod.qualname(fn or mod.tree),
            detail=f"host sync {detail} in loop",
            message=(
                f"host sync `{detail}` inside a loop in "
                f"{mod.qualname(fn or mod.tree)}: this blocks the dispatch "
                "queue every iteration — hoist it, gate it on a log step, "
                "or keep the value on device."
            ),
        )


# ---------------------------------------------------------------------------
# JL005 — recompilation hazards
# ---------------------------------------------------------------------------


def rule_jl005(mod: ModuleInfo) -> Iterator[Finding]:
    """JL005: recompilation hazards at jit boundaries: (a) dict/list-typed
    parameters in jitted signatures — every distinct key set or leaf shape
    retraces; (b) config-named parameters (cfg/config/hparams/...) —
    thread config by closure, not as a traced argument; (c) Python scalar
    defaults on non-static jitted parameters — weak-type churn retraces on
    the first call that passes a concrete dtype; (d) ``jax.jit`` applied
    inside a loop body — a fresh wrapper (usually over a fresh closure)
    retraces and recompiles every iteration.
    """
    seen: Set[int] = set()
    for node, target, kwargs_, decorated in _jit_callsites(mod):
        if target is None or id(target) in seen:
            continue
        seen.add(id(target))
        qual = mod.qualname(target)
        static: Set[str] = set()
        call = node if isinstance(node, ast.Call) else None
        static_idxs: List[int] = []
        if call is not None:
            for k in call.keywords:
                if k.arg == "static_argnums":
                    vals = (
                        k.value.elts
                        if isinstance(k.value, (ast.Tuple, ast.List))
                        else [k.value]
                    )
                    static_idxs = [
                        v.value
                        for v in vals
                        if isinstance(v, ast.Constant)
                        and isinstance(v.value, int)
                    ]
                if k.arg == "static_argnames":
                    for v in ast.walk(k.value):
                        if isinstance(v, ast.Constant) and isinstance(
                            v.value, str
                        ):
                            static.add(v.value)
        params = list(target.args.args)
        for i in static_idxs:
            if i < len(params):
                static.add(params[i].arg)

        defaults = target.args.defaults
        defaulted = params[len(params) - len(defaults):] if defaults else []
        for a, d in zip(defaulted, defaults):
            if a.arg in static:
                continue
            # bools excluded: flag-shaped defaults are JL001's territory
            if isinstance(d, ast.Constant) and isinstance(
                d.value, (int, float)
            ) and not isinstance(d.value, bool):
                yield Finding(
                    rule="JL005",
                    path=mod.path,
                    line=a.lineno,
                    context=qual,
                    detail=f"python scalar param {a.arg!r} in jitted signature",
                    message=(
                        f"jitted {target.name!r} takes Python scalar "
                        f"{a.arg!r} (default {d.value!r}) as a traced arg: "
                        "weak-type promotion retraces when callers pass "
                        "arrays vs literals — mark it static or pass "
                        "jnp.asarray values."
                    ),
                )
        for a in params:
            if a.arg in static:
                continue
            ann = _dotted(a.annotation) if a.annotation is not None else ""
            if ann in _DICTISH_ANNOTATIONS and ann != "Any":
                yield Finding(
                    rule="JL005",
                    path=mod.path,
                    line=a.lineno,
                    context=qual,
                    detail=f"{ann}-typed param {a.arg!r} in jitted signature",
                    message=(
                        f"jitted {target.name!r} takes {a.arg!r}: {ann} — "
                        "every distinct key set / leaf shape is a retrace. "
                        "Bucketed batches should be deliberate (baseline "
                        "this) and config should not be traced at all."
                    ),
                )
            if a.arg in _CONFIG_PARAM_NAMES:
                yield Finding(
                    rule="JL005",
                    path=mod.path,
                    line=a.lineno,
                    context=qual,
                    detail=f"config param {a.arg!r} in jitted signature",
                    message=(
                        f"jitted {target.name!r} threads config object "
                        f"{a.arg!r} through the traced signature: close "
                        "over it (or pass a hashable static) instead."
                    ),
                )

    for node in mod.walk():
        if not isinstance(node, ast.Call):
            continue
        callee = _dotted(node.func)
        is_jit = callee == "jax.jit" or (
            callee in ("functools.partial", "partial")
            and node.args
            and _dotted(node.args[0]) == "jax.jit"
        )
        if not is_jit or not mod.enclosing_loops(node):
            continue
        fn = mod.enclosing_function(node)
        yield Finding(
            rule="JL005",
            path=mod.path,
            line=node.lineno,
            context=mod.qualname(fn or mod.tree),
            detail="jax.jit inside loop body",
            message=(
                "jax.jit applied inside a loop: each iteration builds a "
                "fresh wrapper (and usually a fresh closure) — trace + "
                "compile every pass. Hoist the jit out of the loop."
            ),
        )


# ---------------------------------------------------------------------------
# JL006 — PRNG key reuse
# ---------------------------------------------------------------------------


def _is_key_producer(value: ast.AST) -> bool:
    return isinstance(value, ast.Call) and _dotted(value.func) in (
        "jax.random.PRNGKey", "jax.random.key", *_RNG_DERIVERS
    )


def rule_jl006(mod: ModuleInfo) -> Iterator[Finding]:
    """JL006: PRNG key reuse — the same key consumed by more than one
    draw without an intervening ``jax.random.split``/``fold_in``: (a) one
    key passed to two consumer calls (or twice within one call); (b) a key
    defined outside a loop and consumed inside it without per-iteration
    reassignment; (c) ``jax.random.PRNGKey(<constant>)`` created inside a
    traced context — the same stream on every call, compiled in.

    Reused keys give perfectly correlated "random" draws: dropout masks
    identical across layers/steps, initializations that alias, silently
    degraded training.
    """
    # (c) constant PRNGKey in traced context
    for node in mod.walk():
        if isinstance(node, ast.Call) and _dotted(node.func) in (
            "jax.random.PRNGKey", "jax.random.key"
        ):
            if node.args and isinstance(node.args[0], ast.Constant) and \
                    mod.is_in_traced_context(node):
                fn = mod.enclosing_function(node)
                yield Finding(
                    rule="JL006",
                    path=mod.path,
                    line=node.lineno,
                    context=mod.qualname(fn or mod.tree),
                    detail=f"constant PRNGKey({node.args[0].value!r}) in "
                           "traced context",
                    message=(
                        "jax.random.PRNGKey with a constant seed inside a "
                        "traced function: every call replays the identical "
                        "stream (it is baked into the compiled program) — "
                        "thread a key argument in instead."
                    ),
                )

    for fn in mod.functions:
        keys: Set[str] = set()
        for a in list(fn.args.args) + list(fn.args.kwonlyargs):
            n = a.arg
            if n in ("rng", "key", "prng", "prng_key") or \
                    n.endswith(("_rng", "_key")):
                keys.add(n)
        for node in mod.walk(fn):
            if isinstance(node, ast.Assign) and _is_key_producer(node.value):
                for t in node.targets:
                    for nm in ast.walk(t):
                        if isinstance(nm, ast.Name):
                            keys.add(nm.id)
        if not keys:
            continue

        events: List[Tuple[int, str, str, ast.AST]] = []  # (line, kind, key, node)
        for node in mod.walk(fn):
            if isinstance(node, ast.Assign):
                for t in node.targets:
                    for nm in ast.walk(t):
                        if isinstance(nm, ast.Name) and nm.id in keys:
                            events.append((node.lineno, "assign", nm.id, node))
            elif isinstance(node, ast.Call):
                callee = _dotted(node.func)
                if callee in _RNG_DERIVERS or callee in (
                    "jax.random.PRNGKey", "jax.random.key"
                ):
                    continue
                consumed: List[str] = []
                slots = list(node.args) + [k.value for k in node.keywords]
                # flax .init/.apply fold the collection name into the key,
                # so {"params": rng, "dropout": rng} is safe idiom there —
                # don't count dict values for those callees
                flax_entry = callee.endswith((".init", ".apply"))
                for arg in slots:
                    if isinstance(arg, ast.Name) and arg.id in keys:
                        consumed.append(arg.id)
                    elif isinstance(arg, ast.Dict) and not flax_entry:
                        for v in arg.values:  # rngs={"dropout": rng}
                            if isinstance(v, ast.Name) and v.id in keys:
                                consumed.append(v.id)
                for k in consumed:
                    events.append((node.lineno, "consume", k, node))
                for k in set(consumed):
                    if consumed.count(k) > 1:
                        events.append((node.lineno, "dup", k, node))

        events.sort(key=lambda e: e[0])
        qual = mod.qualname(fn)
        live: Dict[str, int] = {}
        reported: Set[str] = set()
        for line, kind, k, node in events:
            if kind == "assign":
                live[k] = 0
            elif kind == "dup" and f"dup:{k}" not in reported:
                reported.add(f"dup:{k}")
                yield Finding(
                    rule="JL006", path=mod.path, line=line, context=qual,
                    detail=f"key {k!r} passed twice in one call",
                    message=(
                        f"PRNG key {k!r} appears twice in a single call in "
                        f"{qual}: both consumers draw the identical stream "
                        "— jax.random.split it first."
                    ),
                )
            elif kind == "consume":
                loops = mod.enclosing_loops(node)
                in_unrefreshed_loop = False
                for loop in loops:
                    reassigned = any(
                        isinstance(n, ast.Assign)
                        and any(
                            isinstance(t, ast.Name) and t.id == k
                            or (
                                isinstance(t, (ast.Tuple, ast.List))
                                and any(
                                    isinstance(e, ast.Name) and e.id == k
                                    for e in t.elts
                                )
                            )
                            for t in n.targets
                        )
                        for n in mod.walk(loop)
                    )
                    defined_outside = not (
                        loop.lineno <= _first_def_line(fn, k, events)
                        <= _last_line(loop)
                    )
                    if not reassigned and defined_outside:
                        in_unrefreshed_loop = True
                        break
                if in_unrefreshed_loop and f"loop:{k}" not in reported:
                    reported.add(f"loop:{k}")
                    yield Finding(
                        rule="JL006", path=mod.path, line=line, context=qual,
                        detail=f"key {k!r} consumed every loop iteration",
                        message=(
                            f"PRNG key {k!r} is consumed inside a loop in "
                            f"{qual} without per-iteration splitting: every "
                            "iteration draws the identical stream (unless "
                            "the consumer folds in a counter — if it does, "
                            "baseline or suppress this)."
                        ),
                    )
                elif not in_unrefreshed_loop:
                    count = live.get(k, 0)  # params start live at 0 uses
                    live[k] = count + 1
                    if count + 1 == 2 and f"multi:{k}" not in reported:
                        reported.add(f"multi:{k}")
                        yield Finding(
                            rule="JL006", path=mod.path, line=line,
                            context=qual,
                            detail=f"key {k!r} reused by a second consumer",
                            message=(
                                f"PRNG key {k!r} reaches a second consumer "
                                f"in {qual} without jax.random.split: both "
                                "draws are identical."
                            ),
                        )


def _first_def_line(fn: ast.FunctionDef, key: str, events) -> int:
    for line, kind, k, _ in events:
        if kind == "assign" and k == key:
            return line
    return fn.lineno  # parameter


def _last_line(node: ast.AST) -> int:
    return max(
        (getattr(n, "lineno", 0) for n in ast.walk(node)), default=node.lineno
    )


# ---------------------------------------------------------------------------
# JL007 — swallowed exceptions
# ---------------------------------------------------------------------------

_BROAD_EXCEPTION_NAMES = {"Exception", "BaseException"}
_HANDLING_CALL_MARKERS = ("print", "log", "warn", "fail", "record")


def _handler_is_broad(handler: ast.ExceptHandler) -> Optional[str]:
    """The broad type name this handler catches, or None if specific."""
    t = handler.type
    if t is None:
        return "bare except"
    types = t.elts if isinstance(t, ast.Tuple) else [t]
    for e in types:
        name = _dotted(e).split(".")[-1]
        if name in _BROAD_EXCEPTION_NAMES:
            return name
    return None


def _body_swallows(handler: ast.ExceptHandler) -> bool:
    """True when the handler drops the error on the floor: no raise, the
    bound exception name (if any) is never read, and nothing that looks
    like logging/reporting runs."""
    for node in ast.walk(handler):
        if node is handler:
            continue
        if isinstance(node, ast.Raise):
            return False
        if isinstance(node, ast.ExceptHandler):
            return False  # nested try/except: too opaque to judge
        if handler.name and isinstance(node, ast.Name) and \
                isinstance(node.ctx, ast.Load) and node.id == handler.name:
            return False  # the error is used (re-packaged, returned, ...)
        if isinstance(node, ast.Call):
            callee = _dotted(node.func).lower()
            if any(m in callee for m in _HANDLING_CALL_MARKERS):
                return False
    return True


def rule_jl007(mod: ModuleInfo) -> Iterator[Finding]:
    """JL007: swallowed exceptions — an ``except`` catching a broad type
    (bare ``except:``, ``Exception``, ``BaseException``) whose body
    neither re-raises, nor reads the bound error, nor logs: the failure
    silently vanishes.

    In a fault-tolerant training harness every swallowed exception is a
    masked fault: a loader error eaten here bypasses the retry/quarantine
    accounting (training/resilience.py) and surfaces later as a hang or a
    silent data gap. Catch the narrowest type that models the expected
    failure, or route the error through the resilience layer. Scoped to
    the shipped package (``speakingstyle_tpu/``) — tests and one-off
    scripts may probe-and-ignore deliberately.
    """
    p = mod.path.replace("\\", "/")
    if "speakingstyle_tpu/" not in p:
        return
    for node in mod.walk():
        if not isinstance(node, ast.ExceptHandler):
            continue
        broad = _handler_is_broad(node)
        if broad is None or not _body_swallows(node):
            continue
        fn = mod.enclosing_function(node)
        qual = mod.qualname(fn or mod.tree)
        yield Finding(
            rule="JL007",
            path=mod.path,
            line=node.lineno,
            context=qual,
            detail=f"swallowed {broad}",
            message=(
                f"`except {broad}` in {qual} swallows the error (no "
                "re-raise, no use of the exception, no logging): the "
                "failure vanishes. Catch the narrowest expected type, or "
                "log/route it through the resilience layer."
            ),
        )


# ---------------------------------------------------------------------------
# JL008 — compile in hot path
# ---------------------------------------------------------------------------

_JIT_CALL_NAMES = {"jax.jit", "jit", "jax.pjit", "pjit", "jit_program"}
# functions sanctioned to compile in a loop: the AOT startup pattern
# (serving/engine.py precompile) — hoist compiles INTO one of these
_COMPILE_EXEMPT_MARKERS = ("precompile", "warmup", "warm_up")


def _is_handler_name(name: str) -> bool:
    """Request-handler heuristics: http.server's ``do_GET``-style methods,
    and anything named like a handler (``handle_*``, ``*_handler``,
    ``on_request``, ...)."""
    low = name.lower()
    return (name.startswith("do_") and name[3:].isupper()) or \
        "handle" in low or "request" in low


def _is_aot_compile_chain(node: ast.Call) -> bool:
    """``<expr>.lower(...).compile(...)`` — the AOT idiom. Matching the
    full chain (not bare ``.compile()``) keeps re.compile & co. silent."""
    f = node.func
    return (
        isinstance(f, ast.Attribute) and f.attr == "compile"
        and isinstance(f.value, ast.Call)
        and isinstance(f.value.func, ast.Attribute)
        and f.value.func.attr == "lower"
    )


def rule_jl008(mod: ModuleInfo) -> Iterator[Finding]:
    """JL008: XLA compilation in a hot path — ``jax.jit``/``pjit`` or a
    ``.lower(...).compile()`` chain invoked inside a loop, or anywhere in
    a request-handler-shaped function.

    A compile is 10^5-10^7x a dispatch; in a loop it recompiles per
    iteration (a fresh ``jax.jit`` object never shares cache entries with
    the last iteration's), and in a request handler it stalls a live
    request behind XLA. Hoist compilation to startup: build the jits
    once, or AOT-precompile the shape lattice (serving/engine.py). Loops
    inside functions named ``precompile``/``warmup`` are exempt — that IS
    the sanctioned startup pattern.
    """
    for node in mod.walk():
        if not isinstance(node, ast.Call):
            continue
        is_jit = _dotted(node.func) in _JIT_CALL_NAMES
        is_aot = _is_aot_compile_chain(node)
        if not (is_jit and not mod.is_in_traced_context(node)) and not is_aot:
            continue
        qual = mod.qualname(node)
        if any(m in qual.lower() for m in _COMPILE_EXEMPT_MARKERS):
            continue
        what = _dotted(node.func) if is_jit else ".lower().compile()"
        fn = mod.enclosing_function(node)
        in_loop = bool(mod.enclosing_loops(node))
        in_handler = fn is not None and _is_handler_name(fn.name)
        if not in_loop and not in_handler:
            continue
        where = "loop" if in_loop else "request handler"
        yield Finding(
            rule="JL008",
            path=mod.path,
            line=node.lineno,
            context=qual,
            detail=f"{what} in {where}",
            message=(
                f"`{what}` inside a {where} ({qual}): compilation in the "
                "hot path — each hit costs an XLA compile (not a cached "
                "dispatch). Build the jit once at startup, or AOT-"
                "precompile the shape lattice (see serving/engine.py); "
                "precompile/warmup-named functions are exempt."
            ),
        )


# ---------------------------------------------------------------------------
# JL009 — wall clock used for durations
# ---------------------------------------------------------------------------


def rule_jl009(mod: ModuleInfo) -> Iterator[Finding]:
    """JL009: ``time.time()`` used for duration measurement — a
    wall-clock value (or a name assigned from one) appearing as an
    operand of a subtraction.

    ``time.time()`` follows the system clock: NTP slews/steps (and leap
    smearing on cloud VMs) make wall-clock deltas lie, occasionally by
    seconds — poison for latency histograms and throughput windows. Use
    ``time.monotonic()`` (or ``time.perf_counter()``) for every
    duration; wall time is for *timestamps* only (event-log ``ts``
    fields), which are never subtracted.
    """
    wall = {"time.time"}
    for node in mod.walk():
        if isinstance(node, ast.ImportFrom) and node.module == "time":
            for alias in node.names:
                if alias.name == "time":
                    wall.add(alias.asname or "time")

    def is_wall_call(n: ast.AST) -> bool:
        return isinstance(n, ast.Call) and _dotted(n.func) in wall

    stamps: Set[str] = set()
    for node in mod.walk():
        if isinstance(node, ast.Assign) and is_wall_call(node.value):
            for t in node.targets:
                for nm in ast.walk(t):
                    if isinstance(nm, ast.Name):
                        stamps.add(nm.id)

    for node in mod.walk():
        if not isinstance(node, ast.BinOp) or not isinstance(node.op, ast.Sub):
            continue
        hits = []
        for side in (node.left, node.right):
            if is_wall_call(side):
                hits.append("time.time()")
            elif isinstance(side, ast.Name) and side.id in stamps:
                hits.append(side.id)
        if not hits:
            continue
        fn = mod.enclosing_function(node)
        qual = mod.qualname(fn or mod.tree)
        yield Finding(
            rule="JL009",
            path=mod.path,
            line=node.lineno,
            context=qual,
            detail=f"duration arithmetic on wall clock ({', '.join(hits)})",
            message=(
                f"wall-clock subtraction in {qual} ({', '.join(hits)}): "
                "time.time() follows the (NTP-adjusted) system clock, so "
                "deltas can jump or run backwards — measure durations with "
                "time.monotonic()/time.perf_counter(); keep time.time() "
                "for timestamps only."
            ),
        )


# ---------------------------------------------------------------------------
# JL010 — jitted-call timing without a device sync
# ---------------------------------------------------------------------------

_MONO_CLOCK_CALLS = {"time.monotonic", "time.perf_counter"}
# calls that force the device to catch up (or read a result back) —
# any of these inside the timed region makes the timing device-honest
_SYNC_CALL_NAMES = {
    "jax.block_until_ready", "block_until_ready", "jax.device_get",
    "np.asarray", "np.array", "numpy.asarray", "numpy.array",
}


def _jl010_module_jitted(mod: ModuleInfo) -> Set[str]:
    """Names bound to jit-compiled callables anywhere in the file: passed
    to a jax transform, assigned from ``jax.jit(...)``, or assigned from an
    AOT ``.lower(...).compile()`` chain. One module walk, shared by every
    function JL010 looks at."""
    jitted = set(mod._jitted_names)
    for node in mod.walk():
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            if _dotted(node.value.func) in _TRACING_TRANSFORMS or \
                    _is_aot_compile_chain(node.value):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        jitted.add(t.id)
    return jitted


def _jl010_jitted_names(mod: ModuleInfo, fn: ast.FunctionDef,
                        module_jitted: Set[str]) -> Set[str]:
    """Names in/visible-to ``fn`` bound to jit-compiled callables: the
    file-wide set plus functions locally ``@jax.jit``-decorated."""
    jitted = set(module_jitted)
    for sub in mod.walk(fn):
        if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)) and \
                sub in mod._traced:
            jitted.add(sub.name)
    return jitted


def _jl010_is_sync(node: ast.Call) -> bool:
    callee = _dotted(node.func)
    if callee in _SYNC_CALL_NAMES:
        return True
    if isinstance(node.func, ast.Attribute) and node.func.attr in (
        "item", "block_until_ready"
    ):
        return True
    # float(x)/int(x) on a non-constant is a device->host read when x is
    # a device value — the repo's sanctioned explicit-sync idiom
    if isinstance(node.func, ast.Name) and node.func.id in ("float", "int"):
        return bool(node.args) and not isinstance(node.args[0], ast.Constant)
    return False


def rule_jl010(mod: ModuleInfo) -> Iterator[Finding]:
    """JL010: a monotonic-clock duration (``time.monotonic()``/
    ``time.perf_counter()`` subtraction) measured around a jitted call
    with no device sync in the timed region — no
    ``(jax.)block_until_ready``, no ``.item()``/``float()``/
    ``np.asarray``/``device_get`` read of a result.

    jax dispatch is asynchronous: the call returns once the work is
    *enqueued*, so the subtraction times the host's enqueue cost, not
    the device's execution — such numbers are reproducibly, confidently
    wrong (often 100x). Read a result back or ``block_until_ready``
    inside the region, or time at a boundary that already syncs.
    """
    module_jitted = _jl010_module_jitted(mod)
    for fn in mod.functions:
        jitted = _jl010_jitted_names(mod, fn, module_jitted)
        if not jitted:
            continue
        stamp_lines: Dict[str, List[int]] = {}   # name -> clock-assign lines
        jit_lines: List[int] = []
        sync_lines: List[int] = []
        subs: List[Tuple[int, str]] = []         # (line, stamp name)
        for node in mod.walk(fn):
            if isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Call
            ) and _dotted(node.value.func) in _MONO_CLOCK_CALLS:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        stamp_lines.setdefault(t.id, []).append(node.lineno)
            elif isinstance(node, ast.Call):
                if _jl010_is_sync(node):
                    sync_lines.append(node.lineno)
                elif isinstance(node.func, ast.Name) and \
                        node.func.id in jitted:
                    jit_lines.append(node.lineno)
            if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub):
                for side in (node.left, node.right):
                    if isinstance(side, ast.Name) and side.id in stamp_lines:
                        subs.append((node.lineno, side.id))
        qual = mod.qualname(fn)
        reported: Set[Tuple[int, str]] = set()
        for line, stamp in subs:
            starts = [s for s in stamp_lines[stamp] if s < line]
            if not starts:
                continue
            start = max(starts)  # the stamp assignment this delta closes
            if not any(start < l <= line for l in jit_lines):
                continue
            if any(start < l <= line for l in sync_lines):
                continue
            if (start, stamp) in reported:
                continue
            reported.add((start, stamp))
            yield Finding(
                rule="JL010",
                path=mod.path,
                line=line,
                context=qual,
                detail=f"unsynced jitted-call timing via {stamp!r}",
                message=(
                    f"duration from {stamp!r} in {qual} times a jitted "
                    "call with no sync in the region: async dispatch "
                    "returns at enqueue, so this measures host overhead, "
                    "not execution — block_until_ready (or read a result "
                    "back) before taking the end timestamp."
                ),
            )


# ---------------------------------------------------------------------------
# JL011 — unbounded queues in serving code
# ---------------------------------------------------------------------------

_BOUNDABLE_QUEUES = {
    "queue.Queue", "Queue", "queue.LifoQueue", "LifoQueue",
    "queue.PriorityQueue", "PriorityQueue",
}
_UNBOUNDABLE_QUEUES = {"queue.SimpleQueue", "SimpleQueue"}


def rule_jl011(mod: ModuleInfo) -> Iterator[Finding]:
    """JL011: unbounded queue construction under
    ``speakingstyle_tpu/serving/`` — ``queue.Queue()`` (or LifoQueue/
    PriorityQueue) with no ``maxsize``, a constant ``maxsize <= 0``
    (stdlib semantics: infinite), or ``queue.SimpleQueue`` (which cannot
    be bounded at all).

    Serving backpressure is a *contract*: load-shedding watermarks and
    the 429 path only mean something if every queue between admission
    and the device has a capacity to measure against. An unbounded queue
    silently converts overload into unbounded latency (and memory)
    instead of an honest shed — the exact failure mode the fleet
    router's ``serve_shed_total`` exists to prevent. Bound the queue
    (``queue.Queue(maxsize=...)``) and admit through a stop-aware
    ``bounded_put`` (data/prefetch.py).
    """
    p = mod.path.replace("\\", "/")
    if "speakingstyle_tpu/serving/" not in p:
        return
    for node in mod.walk():
        if not isinstance(node, ast.Call):
            continue
        callee = _dotted(node.func)
        detail = None
        if callee in _UNBOUNDABLE_QUEUES:
            detail = f"{callee} (cannot be bounded)"
        elif callee in _BOUNDABLE_QUEUES:
            size = None
            if node.args:
                size = node.args[0]
            for kw in node.keywords:
                if kw.arg == "maxsize":
                    size = kw.value
            if size is None:
                detail = f"{callee}() with no maxsize"
            elif isinstance(size, ast.Constant) and (
                not isinstance(size.value, int) or size.value <= 0
            ):
                detail = f"{callee}(maxsize={size.value!r})"
        if detail is None:
            continue
        fn = mod.enclosing_function(node)
        qual = mod.qualname(fn or mod.tree)
        yield Finding(
            rule="JL011",
            path=mod.path,
            line=node.lineno,
            context=qual,
            detail=f"unbounded {detail}",
            message=(
                f"unbounded queue `{detail}` in serving code ({qual}): "
                "every serving queue must be bounded or backpressure is "
                "meaningless — overload becomes unbounded latency/memory "
                "instead of an honest 429 shed. Pass a positive maxsize "
                "and enqueue via the stop-aware bounded_put."
            ),
        )


# ---------------------------------------------------------------------------
# JL012 — unbounded caches in serving code
# ---------------------------------------------------------------------------

_LRU_CACHE_NAMES = {"functools.lru_cache", "lru_cache"}
_ALWAYS_UNBOUNDED_CACHES = {"functools.cache", "cache"}


def _target_names(target: ast.AST) -> Iterator[str]:
    """Terminal identifiers of an assignment target: ``self._mel_cache``
    -> ``_mel_cache``; tuple targets yield each element's name."""
    if isinstance(target, ast.Name):
        yield target.id
    elif isinstance(target, ast.Attribute):
        yield target.attr
    elif isinstance(target, (ast.Tuple, ast.List)):
        for e in target.elts:
            yield from _target_names(e)


def _lru_cache_unbounded(node: ast.Call) -> bool:
    """``lru_cache(maxsize=None)`` / ``lru_cache(None)`` — the bare call
    keeps the stdlib's bounded default of 128, so only an explicit None
    is the hazard."""
    size = node.args[0] if node.args else None
    for kw in node.keywords:
        if kw.arg == "maxsize":
            size = kw.value
    return isinstance(size, ast.Constant) and size.value is None


def rule_jl012(mod: ModuleInfo) -> Iterator[Finding]:
    """JL012: unbounded caches under ``speakingstyle_tpu/serving/`` —
    ``functools.lru_cache(maxsize=None)`` / ``functools.cache`` (which is
    exactly that), or an empty ``{}``/``dict()`` assigned to a target
    whose name contains "cache".

    The JL011 rule for state that *content* fills rather than requests:
    a serving process caching per-request payloads (reference styles,
    mels, parsed uploads) in an unbounded structure converts distinct-
    content traffic into unbounded memory — an OOM kill on a long-lived
    replica, the slowest possible shed. Serving caches must be bounded
    with explicit eviction (the StyleService's content-addressed LRU,
    ``serve.style.cache_capacity`` + ``serve_style_cache_evictions_total``,
    is the house pattern).
    """
    p = mod.path.replace("\\", "/")
    if "speakingstyle_tpu/serving/" not in p:
        return
    # bare @functools.cache / @cache decorators (no call parentheses)
    for fn in mod.functions:
        for dec in fn.decorator_list:
            if not isinstance(dec, ast.Call) and \
                    _dotted(dec) in _ALWAYS_UNBOUNDED_CACHES:
                yield Finding(
                    rule="JL012",
                    path=mod.path,
                    line=dec.lineno,
                    context=mod.qualname(fn),
                    detail=f"unbounded {_dotted(dec)} (never evicts)",
                    message=(
                        f"`@{_dotted(dec)}` in serving code caches every "
                        "distinct call unboundedly — use "
                        "lru_cache(maxsize=N) or a capacity-limited LRU "
                        "(serving/style.py)."
                    ),
                )
    for node in mod.walk():
        if isinstance(node, ast.Call):
            callee = _dotted(node.func)
            detail = None
            if callee in _ALWAYS_UNBOUNDED_CACHES:
                detail = f"{callee} (never evicts)"
            elif callee in _LRU_CACHE_NAMES and _lru_cache_unbounded(node):
                detail = f"{callee}(maxsize=None)"
            if detail is None:
                continue
            fn = mod.enclosing_function(node)
            yield Finding(
                rule="JL012",
                path=mod.path,
                line=node.lineno,
                context=mod.qualname(fn or mod.tree),
                detail=f"unbounded {detail}",
                message=(
                    f"unbounded cache `{detail}` in serving code: per-"
                    "request content accumulates without eviction — bound "
                    "the cache (lru_cache(maxsize=N), or a capacity-"
                    "limited LRU like serving/style.py's) so memory is a "
                    "function of capacity, not traffic history."
                ),
            )
        elif isinstance(node, (ast.Assign, ast.AnnAssign)):
            value = node.value
            is_empty_dict = isinstance(value, ast.Dict) and not value.keys
            is_dict_call = (
                isinstance(value, ast.Call)
                and _dotted(value.func) == "dict" and not value.args
                and not value.keywords
            )
            if not (is_empty_dict or is_dict_call):
                continue
            targets = (
                node.targets if isinstance(node, ast.Assign)
                else [node.target]
            )
            for t in targets:
                for name in _target_names(t):
                    if "cache" not in name.lower():
                        continue
                    fn = mod.enclosing_function(node)
                    yield Finding(
                        rule="JL012",
                        path=mod.path,
                        line=node.lineno,
                        context=mod.qualname(fn or mod.tree),
                        detail=f"dict cache {name!r} with no bound",
                        message=(
                            f"`{name}` is a plain dict used as a cache in "
                            "serving code: nothing ever evicts, so memory "
                            "grows with distinct request content. Use a "
                            "bounded LRU (OrderedDict + capacity + "
                            "eviction counter — see serving/style.py)."
                        ),
                    )


# ---------------------------------------------------------------------------
# JL013 — unbounded blocking waits in serving code
# ---------------------------------------------------------------------------


def rule_jl013(mod: ModuleInfo) -> Iterator[Finding]:
    """JL013: a blocking wait with no timeout under
    ``speakingstyle_tpu/serving/`` — ``fut.result()`` with no arguments,
    or a zero-argument ``q.get()`` (the ``queue.Queue`` signature; a
    ``dict.get(key)`` carries a positional argument and is not matched)
    — neither carrying a ``timeout=``.

    Serving threads that wait forever undo the resilience contract: the
    supervisor can fail a replica, requeue its batch, and resolve every
    future with a structured error, but a handler parked on a bare
    ``future.result()`` (or a worker on a bare ``queue.get()``) only
    benefits if *someone* resolves/feeds it — a bookkeeping bug or a
    lost wakeup then hangs the connection with no 5xx ever sent. Every
    wait in the serving tree must carry a deadline (the class deadline
    budget + grace for request futures; a poll interval for queues) so
    the worst case is a timely 504, not a stuck thread.
    """
    p = mod.path.replace("\\", "/")
    if "speakingstyle_tpu/serving/" not in p:
        return
    for node in mod.walk():
        if not isinstance(node, ast.Call):
            continue
        func = node.func
        if not isinstance(func, ast.Attribute):
            continue
        if func.attr not in ("result", "get"):
            continue
        # zero positional args only: dict.get(key[, default]) and
        # result(timeout) positionally both carry args and are bounded
        # (or at least deliberate); the bare no-arg call is the hazard
        if node.args:
            continue
        if any(kw.arg == "timeout" for kw in node.keywords):
            continue
        fn = mod.enclosing_function(node)
        qual = mod.qualname(fn or mod.tree)
        recv = _dotted(func.value) or "<expr>"
        yield Finding(
            rule="JL013",
            path=mod.path,
            line=node.lineno,
            context=qual,
            detail=f"bare {recv}.{func.attr}() with no timeout",
            message=(
                f"`{recv}.{func.attr}()` in serving code ({qual}) blocks "
                "forever: if the producer dies or a bookkeeping bug drops "
                "the wakeup, this thread hangs with no 5xx ever sent. "
                "Pass timeout= (request futures: the class deadline "
                "budget + grace; queues: a poll interval) and map the "
                "timeout to a structured error."
            ),
        )


# ---------------------------------------------------------------------------
# JL014 — hard single-device pinning in training/data code
# ---------------------------------------------------------------------------


_DEVICE_LIST_CALLS = ("jax.devices", "jax.local_devices")


def _device_pin_spelling(node: ast.AST, pinned_names: Set[str]) -> str:
    """The pinned-device spelling if ``node`` hard-pins one device
    (``jax.devices()[i]`` / ``jax.local_devices()[i]``, or a name
    assigned from one), else ''."""
    if isinstance(node, ast.Subscript):
        base = _dotted(node.value)
        if base in _DEVICE_LIST_CALLS:
            return f"{base}()[...]"
    if isinstance(node, ast.Name) and node.id in pinned_names:
        return node.id
    return ""


def rule_jl014(mod: ModuleInfo) -> Iterator[Finding]:
    """JL014: hard single-device pinning under ``training/`` or ``data/``:
    ``jax.device_put(x, jax.devices()[0])`` — the device argument is a
    subscript of ``jax.devices()``/``jax.local_devices()``, directly or
    through a variable assigned from one.

    Now that the trainer runs on a mesh, placement is a *sharding*
    contract: the prefetcher device_puts against the batch
    NamedSharding, the state is laid out by train_state_shardings, and
    XLA spreads both across the mesh. A device_put pinned to device 0
    silently defeats that — every batch (and the compute consuming it)
    funnels onto one chip of an N-chip mesh, so the run stays correct
    while throughput divides by N. Pass the mesh's NamedSharding
    (``batch_sharding(mesh)``) instead, or omit the device and let jax
    place single-chip transfers by default.
    """
    p = mod.path.replace("\\", "/")
    if "training/" not in p and "data/" not in p:
        return
    # names assigned (lexically, anywhere in the file) from a
    # jax.devices()/jax.local_devices() subscript
    pinned: Set[str] = set()
    for node in mod.walk():
        if isinstance(node, ast.Assign) and isinstance(
            node.value, ast.Subscript
        ):
            if _dotted(node.value.value) in _DEVICE_LIST_CALLS:
                pinned |= {
                    t.id for t in node.targets if isinstance(t, ast.Name)
                }
    for node in mod.walk():
        if not isinstance(node, ast.Call):
            continue
        callee = _dotted(node.func)
        if callee not in ("jax.device_put", "device_put"):
            continue
        dev_args = list(node.args[1:]) + [
            kw.value for kw in node.keywords if kw.arg == "device"
        ]
        for arg in dev_args:
            pin = _device_pin_spelling(arg, pinned)
            if not pin:
                continue
            fn = mod.enclosing_function(node)
            qual = mod.qualname(fn or mod.tree)
            yield Finding(
                rule="JL014",
                path=mod.path,
                line=node.lineno,
                context=qual,
                detail=f"device_put pinned to {pin}",
                message=(
                    f"`device_put(..., {pin})` in {qual} hard-pins the "
                    "transfer to one device: under a mesh this funnels "
                    "every batch onto a single chip (1/N throughput). "
                    "Pass the mesh's NamedSharding "
                    "(batch_sharding(mesh)) or omit the device."
                ),
            )
            break


# ---------------------------------------------------------------------------
# JL015 — fresh ndarray allocation in the serving hot path
# ---------------------------------------------------------------------------


_FRESH_ALLOC_CALLS = {
    "np.zeros", "np.full", "np.pad", "np.concatenate",
    "numpy.zeros", "numpy.full", "numpy.pad", "numpy.concatenate",
}


def _is_dispatch_shaped(name: str) -> bool:
    """Hot-path heuristics for serving code: request handlers (JL008's
    definition) plus dispatch/emit-loop workers (``_dispatch``,
    ``dispatch_loop``, ``stream_wav``-style emitters)."""
    low = name.lower()
    return _is_handler_name(name) or "dispatch" in low or "emit" in low


def rule_jl015(mod: ModuleInfo) -> Iterator[Finding]:
    """JL015: fresh ndarray allocation in the serving hot path —
    ``np.zeros``/``np.full``/``np.pad``/``np.concatenate`` inside a loop,
    or anywhere in a dispatch-/handler-shaped function, under
    ``speakingstyle_tpu/serving/``.

    The steady-state serving claim is *allocation-free*: every padded
    staging buffer is leased from the per-bucket BufferPool
    (serving/pool.py) and written in place, so the dispatch loop's
    allocator traffic is zero after warmup (``serve_pool_allocs_total``
    flat).  A fresh ``np.zeros``/``np.pad`` per request reintroduces
    malloc/free (and page-zeroing) jitter exactly where the p999 is
    made, and ``np.concatenate`` re-materializes whole utterances the
    streaming path deliberately emits window-by-window.  Lease from the
    pool and ``np.copyto``/slice-assign instead.  Functions named
    ``precompile``/``warmup`` are exempt — startup may allocate freely.
    """
    p = mod.path.replace("\\", "/")
    if "speakingstyle_tpu/serving/" not in p:
        return
    for node in mod.walk():
        if not isinstance(node, ast.Call):
            continue
        callee = _dotted(node.func)
        if callee not in _FRESH_ALLOC_CALLS:
            continue
        qual = mod.qualname(node)
        if any(m in qual.lower() for m in _COMPILE_EXEMPT_MARKERS):
            continue
        fn = mod.enclosing_function(node)
        in_loop = bool(mod.enclosing_loops(node))
        in_dispatch = fn is not None and _is_dispatch_shaped(fn.name)
        if not in_loop and not in_dispatch:
            continue
        where = "loop" if in_loop else "dispatch/handler function"
        yield Finding(
            rule="JL015",
            path=mod.path,
            line=node.lineno,
            context=qual,
            detail=f"{callee} in {where}",
            message=(
                f"`{callee}` inside a {where} ({qual}): a fresh ndarray "
                "per request breaks the allocation-free steady state — "
                "malloc + page-zero jitter lands straight in the latency "
                "tail. Lease a padded buffer from the BufferPool "
                "(serving/pool.py) and write in place; "
                "precompile/warmup-named functions are exempt."
            ),
        )


_SLEEP_CALLS = {"time.sleep", "sleep"}


def rule_jl016(mod: ModuleInfo) -> Iterator[Finding]:
    """JL016: bare ``time.sleep()`` in a loop under
    ``speakingstyle_tpu/serving/`` — supervision/policy loops must park
    on a stop-aware wait.

    Serving-side background loops (the fleet supervisor's watchdog
    sweep, the autoscaler's policy tick, re-warm backoff) all follow one
    idiom: block on ``Event.wait(timeout)`` or ``Condition.wait(timeout)``
    so that ``close()`` can set/notify and the thread exits NOW, not up
    to a full tick later. A bare ``time.sleep`` in such a loop is
    uninterruptible — drain and shutdown inherit its latency, and a
    SIGTERM'd process misses its drain deadline because a policy thread
    was napping. One-shot sleeps outside loops (a close-path settle, an
    injected fault's deliberate stall) are not supervision cadence and
    are not flagged.
    """
    p = mod.path.replace("\\", "/")
    if "speakingstyle_tpu/serving/" not in p:
        return
    for node in mod.walk():
        if not isinstance(node, ast.Call):
            continue
        if _dotted(node.func) not in _SLEEP_CALLS:
            continue
        if not mod.enclosing_loops(node):
            continue
        qual = mod.qualname(node)
        yield Finding(
            rule="JL016",
            path=mod.path,
            line=node.lineno,
            context=qual,
            detail="time.sleep in loop",
            message=(
                f"`time.sleep` inside a loop ({qual}): a supervision/"
                "policy loop must park on a stop-aware "
                "`Event.wait(timeout)` (or `Condition.wait`) so close()/"
                "drain interrupts it immediately — a bare sleep holds "
                "shutdown hostage for up to a full tick."
            ),
        )


# ---------------------------------------------------------------------------
# JL017 — non-atomic persistent writes to checkpoint/artifact paths
# ---------------------------------------------------------------------------


_PERSIST_SAVE_CALLS = {"np.save", "np.savez", "numpy.save", "numpy.savez"}
# path spellings that mark a durable artifact worth crash-safety
_ARTIFACT_MARKERS = (
    "ckpt", "checkpoint", "manifest", "weights", "baseline", "snapshot",
    "artifact",
)
# spellings that mark the temp half of a temp+replace pattern
_TEMP_MARKERS = ("tmp", "temp", "part")
_ATOMIC_RENAME_CALLS = {"os.replace", "os.rename"}


def _path_spelling(node: ast.AST) -> str:
    """Every lexical fragment of a path expression, lowercased: string
    constants, variable names, attribute chains, f-string parts — enough
    to recognize ``ckpt_path`` / ``f"{d}/manifest.json"`` shapes without
    evaluating anything."""
    parts: List[str] = []
    for n in ast.walk(node):
        if isinstance(n, ast.Constant) and isinstance(n.value, str):
            parts.append(n.value)
        elif isinstance(n, ast.Name):
            parts.append(n.id)
        elif isinstance(n, ast.Attribute):
            parts.append(n.attr)
    return " ".join(parts).lower()


def _scope_has_atomic_rename(mod: "ModuleInfo", node: ast.AST) -> bool:
    """True when the enclosing function (or the module body, for
    top-level code) performs an ``os.replace``/``os.rename`` — the
    signature of the temp-file + atomic-publish idiom."""
    scope = mod.enclosing_function(node) or mod.tree
    return any(
        isinstance(n, ast.Call) and _dotted(n.func) in _ATOMIC_RENAME_CALLS
        for n in mod.walk(scope)
    )


def rule_jl017(mod: ModuleInfo) -> Iterator[Finding]:
    """JL017: non-atomic persistent writes — ``open(path, "w"/"wb")`` or
    ``np.save``/``np.savez`` on a checkpoint/artifact-shaped path with
    no temp + ``os.replace`` in the enclosing scope, under
    ``speakingstyle_tpu/training/`` or ``speakingstyle_tpu/serving/``.

    A durable artifact (checkpoint manifest, weights export, committed
    baseline, capacity snapshot) must appear ATOMICALLY: a process
    killed mid-``write()`` otherwise leaves a torn file that the next
    reader sees as corrupt — precisely the failure the checkpoint
    integrity layer (training/checkpoint.py) exists to catch, and one
    that rename-into-place eliminates for free on POSIX. Write to
    ``<name>.tmp`` in the same directory, flush+fsync, then
    ``os.replace``. Writes whose path spelling is already temp-marked
    (``tmp``/``temp``/``part``) are the first half of that idiom and
    exempt, as is any write in a scope that also calls
    ``os.replace``/``os.rename``.
    """
    p = mod.path.replace("\\", "/")
    if ("speakingstyle_tpu/training/" not in p
            and "speakingstyle_tpu/serving/" not in p):
        return
    for node in mod.walk():
        if not isinstance(node, ast.Call):
            continue
        callee = _dotted(node.func)
        path_arg = None
        if callee == "open" and node.args:
            mode = ""
            if len(node.args) > 1 and isinstance(node.args[1], ast.Constant) \
                    and isinstance(node.args[1].value, str):
                mode = node.args[1].value
            for kw in node.keywords:
                if kw.arg == "mode" and isinstance(kw.value, ast.Constant) \
                        and isinstance(kw.value.value, str):
                    mode = kw.value.value
            if "w" not in mode:
                continue  # reads and appends are not publishes
            path_arg = node.args[0]
        elif callee in _PERSIST_SAVE_CALLS and node.args:
            path_arg = node.args[0]
        else:
            continue
        spelling = _path_spelling(path_arg)
        if not any(m in spelling for m in _ARTIFACT_MARKERS):
            continue
        if any(m in spelling for m in _TEMP_MARKERS):
            continue  # the temp half of temp+replace
        if _scope_has_atomic_rename(mod, node):
            continue
        qual = mod.qualname(node)
        yield Finding(
            rule="JL017",
            path=mod.path,
            line=node.lineno,
            context=qual,
            detail=f"non-atomic {callee} to artifact path",
            message=(
                f"`{callee}` writes a checkpoint/artifact-shaped path "
                f"in place ({qual}): a crash mid-write leaves a torn "
                "file the next reader sees as CORRUPT. Publish "
                "atomically — write `<name>.tmp`, flush+fsync, then "
                "`os.replace` (training/checkpoint.py's manifest "
                "writer is the reference idiom)."
            ),
        )


# ---------------------------------------------------------------------------
# JL018 — XLA compilation outside the program registry
# ---------------------------------------------------------------------------


_RAW_JIT_SPELLINGS = {"jax.jit", "jax.pjit", "jax.experimental.pjit.pjit"}
_JIT_IMPORT_NAMES = {"jit", "pjit"}
_REGISTRY_PATH_MARKER = "parallel/registry.py"


def _jl018_in_scope(path: str) -> bool:
    """The enforced tree: the package, except the registry. Tests,
    scripts/, and anything outside the package may spell jax.jit (their
    compiles are fixtures, not production programs)."""
    p = path.replace("\\", "/")
    return "speakingstyle_tpu/" in p and _REGISTRY_PATH_MARKER not in p


def rule_jl018(mod: ModuleInfo) -> Iterator[Finding]:
    """JL018: XLA compilation outside ``parallel/registry.py`` — a
    reference to ``jax.jit``/``jax.pjit`` (call, decorator,
    ``functools.partial`` argument, or bare attribute), a
    ``from jax import jit``-style import, or a ``.lower(...).compile()``
    AOT chain, anywhere under ``speakingstyle_tpu/``.

    The ProgramRegistry (parallel/registry.py) is the ONE guarded entry
    point where XLA programs are built: it owns the cache-key semantics
    ("did we already build this program?" has one answer), the compile
    counters, the persistent-cache hookup, and the sharding-spec card
    table behind ``GET /debug/programs``. A stray ``jax.jit`` anywhere
    else re-opens a side door the zero-steady-state-compiles invariant
    (JL008) cannot see through. Route AOT compiles through
    ``ProgramRegistry.compile`` and jit-on-first-call wrappers through
    ``jit_program``. Functions named ``precompile``/``warmup`` are
    exempt (startup fixtures); the tree baseline for this rule is zero
    and must stay zero.
    """
    if not _jl018_in_scope(mod.path):
        return

    def _exempt(node: ast.AST) -> bool:
        qual = mod.qualname(node)
        return any(m in qual.lower() for m in _COMPILE_EXEMPT_MARKERS)

    def _finding(node: ast.AST, what: str) -> Finding:
        return Finding(
            rule="JL018",
            path=mod.path,
            line=node.lineno,
            context=mod.qualname(node),
            detail=f"{what} outside registry",
            message=(
                f"`{what}` outside parallel/registry.py "
                f"({mod.qualname(node)}): the ProgramRegistry is the one "
                "compile entry point — use ProgramRegistry.compile for "
                "AOT programs or jit_program for jit-on-call wrappers "
                "so cache keys, compile counters, persistent-cache "
                "wiring, and /debug/programs cards stay complete."
            ),
        )

    for node in mod.walk():
        if isinstance(node, ast.ImportFrom):
            if node.module and node.module.split(".")[0] == "jax":
                for alias in node.names:
                    if alias.name in _JIT_IMPORT_NAMES:
                        yield _finding(node, f"from {node.module} "
                                             f"import {alias.name}")
        elif isinstance(node, ast.Attribute):
            if _dotted(node) in _RAW_JIT_SPELLINGS and not _exempt(node):
                yield _finding(node, _dotted(node))
        elif isinstance(node, ast.Call):
            if _is_aot_compile_chain(node) and not _exempt(node):
                yield _finding(node, ".lower().compile()")


# ---------------------------------------------------------------------------
# JL019 — full-utterance accumulation (append-in-loop + concatenate)
# ---------------------------------------------------------------------------


_CONCAT_CALLS = {
    "np.concatenate", "numpy.concatenate", "jnp.concatenate",
    "jax.numpy.concatenate",
}
_ACCUM_METHODS = {"append", "extend"}


def rule_jl019(mod: ModuleInfo) -> Iterator[Finding]:
    """JL019: full-utterance accumulation under
    ``speakingstyle_tpu/serving/`` — a list ``.append``/``.extend``-ed
    inside a loop and then handed to ``np.concatenate`` /
    ``jnp.concatenate`` in the same scope.

    The bounded-memory contract for served audio is structural: the
    streaming path emits overlap-trimmed windows (serving/streaming.py)
    and the long-form path emits crossfaded seams (serving/longform.py),
    so at no point does the host hold a whole utterance — let alone a
    chapter — as one buffer.  The accumulate-then-concat shape
    (``pieces.append(wav)`` in the chunk loop, ``np.concatenate(pieces)``
    after it) silently re-materializes that buffer: memory scales with
    requested AUDIO LENGTH instead of with the in-flight window count,
    and one hour-long chapter OOMs the serving host.  Yield the pieces
    instead.  JL015 flags a ``concatenate`` *call* inside a loop or
    handler; this rule catches the spelling where the call sits after
    the loop and only the appends are inside it.  Functions named
    ``precompile``/``warmup`` are exempt (startup fixtures); the tree
    baseline for this rule is zero and must stay zero.
    """
    p = mod.path.replace("\\", "/")
    if "speakingstyle_tpu/serving/" not in p:
        return
    # scope id -> names of lists grown inside a loop in that scope
    grown: Dict[int, Set[str]] = {}
    for node in mod.walk():
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if not (isinstance(f, ast.Attribute) and f.attr in _ACCUM_METHODS
                and isinstance(f.value, ast.Name)):
            continue
        if not mod.enclosing_loops(node):
            continue
        scope = mod.enclosing_function(node)
        grown.setdefault(id(scope), set()).add(f.value.id)
    if not grown:
        return
    for node in mod.walk():
        if not isinstance(node, ast.Call):
            continue
        callee = _dotted(node.func)
        if callee not in _CONCAT_CALLS or not node.args:
            continue
        qual = mod.qualname(node)
        if any(m in qual.lower() for m in _COMPILE_EXEMPT_MARKERS):
            continue
        scope = mod.enclosing_function(node)
        names = grown.get(id(scope), set())
        arg = node.args[0]
        arg_names = {n.id for n in ast.walk(arg) if isinstance(n, ast.Name)}
        for name in sorted(arg_names & names):
            yield Finding(
                rule="JL019",
                path=mod.path,
                line=node.lineno,
                context=qual,
                detail=f"{callee}({name}) after loop accumulation",
                message=(
                    f"`{callee}({name})` consumes a list grown inside a "
                    f"loop ({qual}): accumulate-then-concat materializes "
                    "the full utterance/chapter host-side, so memory "
                    "scales with audio length instead of the in-flight "
                    "window bound. Yield the pieces as they are produced "
                    "(streaming.stream_wav / longform.Stitcher are the "
                    "reference idioms)."
                ),
            )


# ---------------------------------------------------------------------------
# JL020–JL023 — lock-discipline rules over the class-concurrency model
# ---------------------------------------------------------------------------


def _concurrency_in_scope(mod: ModuleInfo) -> bool:
    """Package code only: tests/ create deliberately ad-hoc threads and
    toy locks that would drown the signal."""
    p = mod.path.replace("\\", "/")
    return "speakingstyle_tpu/" in p and "tests/" not in p


def _conc_model(mod: ModuleInfo):
    from speakingstyle_tpu.analysis import concurrency

    return concurrency.module_model(mod)


def rule_jl020(mod: ModuleInfo) -> Iterator[Finding]:
    """JL020: torn-state race — an attribute accessed under a lock in
    one method and read/written lock-free in another, where the class's
    methods run on more than one thread.

    The guarded-by model (analysis/concurrency.py) classifies every
    attribute site by the ``with self._lock:`` scopes around it, widened
    by helper call-through (a private helper whose every caller holds L
    is analyzed with L at entry), and binds ``rep.state``-style local
    receivers to the class that declares the attribute. A finding needs
    all of: a guarded site, a lock-free site in a *different* method
    (``__init__`` excluded — construction happens-before), a write
    somewhere, and a thread-reachable method among the sites. Events,
    queues, obs.registry metrics, and the lock objects themselves are
    exempt (their thread-safety is internal); deliberate single-reader
    patterns get ``# jaxlint: disable=JL020 reason=...``.
    """
    if not _concurrency_in_scope(mod):
        return
    model = _conc_model(mod)
    # (owner class, attr) -> [(site, MethodModel, effective locks)]
    groups: Dict[Tuple[str, str], List] = {}
    for cls in model.classes.values():
        for mm in cls.methods.values():
            for s in mm.sites:
                if s.owner == "self":
                    owner = cls.name
                else:
                    owner = model.unique_attr_owner.get(s.attr)
                    if owner is None:
                        continue
                owner_cls = model.classes.get(owner)
                if owner_cls is None or s.attr not in owner_cls.init_attrs:
                    continue
                kind = owner_cls.attr_kinds.get(s.attr)
                if kind is not None:
                    continue  # lock/event/queue/metric: exempt kinds
                eff = s.locks | mm.entry_locks
                groups.setdefault((owner, s.attr), []).append((s, mm, eff))
    for (owner, attr), entries in sorted(groups.items()):
        guarded_methods = {mm.qualname for s, mm, eff in entries if eff}
        if not guarded_methods:
            continue
        # the write that makes a race possible must happen outside
        # __init__ — construction happens-before every thread start, so
        # an attribute assigned once and then only read is immutable
        # shared state, not a race
        if not any(s.is_write for s, mm, _ in entries
                   if mm.name != "__init__"):
            continue
        if not any(mm.thread_reachable for _, mm, _ in entries):
            continue
        locks = sorted(set().union(
            *[eff for _, _, eff in entries if eff]
        ))
        reported: Set[str] = set()
        for s, mm, eff in entries:
            if eff or mm.name == "__init__":
                continue
            other_guarded = guarded_methods - {mm.qualname}
            if not other_guarded:
                continue
            if mm.qualname in reported:
                continue
            reported.add(mm.qualname)
            kind = "write" if s.is_write else "read"
            yield Finding(
                rule="JL020",
                path=mod.path,
                line=s.lineno,
                context=mm.qualname,
                detail=f"{owner}.{attr} lock-free in {mm.qualname}",
                message=(
                    f"`{owner}.{attr}` is guarded by "
                    f"{'/'.join(locks)} in "
                    f"{'/'.join(sorted(other_guarded))} but "
                    f"{kind} lock-free in {mm.qualname} — a torn-state "
                    "race once those methods run on different threads. "
                    "Take the lock around this access, or mark a "
                    "provably benign pattern with "
                    "`# jaxlint: disable=JL020 reason=...`."
                ),
            )


def rule_jl021(mod: ModuleInfo) -> Iterator[Finding]:
    """JL021: blocking call while holding a lock — future.result,
    Event.wait, queue get/put, socket/HTTP send, subprocess, sleep, or
    a registry/XLA compile inside a ``with self._lock:`` scope (or a
    helper that inherits the lock at entry). Every other thread that
    touches the lock convoys behind the slow call; if the blocked-on
    resource needs the same lock to make progress, it is a deadlock.
    ``Condition.wait`` on the held lock releases it while parked and is
    exempt; ``SimpleQueue.put`` cannot block and is exempt. Deliberate
    holds (the registry's serialize-all-compiles lock) get
    ``# jaxlint: disable=JL021 reason=...``.
    """
    if not _concurrency_in_scope(mod):
        return
    model = _conc_model(mod)
    for cls in sorted(model.classes.values(), key=lambda c: c.lineno):
        for mm in sorted(cls.methods.values(), key=lambda m: m.lineno):
            for b in mm.blocking:
                eff = set(b.locks) | set(mm.entry_locks)
                if not eff:
                    continue
                locks = "/".join(sorted(eff))
                yield Finding(
                    rule="JL021",
                    path=mod.path,
                    line=b.lineno,
                    context=mm.qualname,
                    detail=f"{b.desc} under {locks}",
                    message=(
                        f"{mm.qualname} makes a blocking call "
                        f"({b.desc}) while holding {locks}: every "
                        "thread touching that lock convoys behind it, "
                        "and a dependency back onto the lock deadlocks. "
                        "Move the call outside the critical section, or "
                        "mark a deliberate serialization point with "
                        "`# jaxlint: disable=JL021 reason=...`."
                    ),
                )


def rule_jl022(mod: ModuleInfo) -> Iterator[Finding]:
    """JL022: lock-order cycle — nested acquisitions in source order
    (``with self._a:`` inside ``with self._b:``, helper call-through,
    and cross-class call-through on typed attributes) are edges in the
    lock-order graph; a cycle is a latent deadlock regardless of
    schedule luck. The module-local graph is checked here; the
    program-wide graph is built by ``python -m
    speakingstyle_tpu.analysis.cli lockorder --write`` into
    analysis/lockorder.json, which ``--check`` keeps fresh and the
    runtime TrackedLock witness (obs/locks.py) enforces.
    """
    if not _concurrency_in_scope(mod):
        return
    from speakingstyle_tpu.analysis import concurrency

    model = _conc_model(mod)
    edges = concurrency.lock_edges([model])
    cycle = concurrency.find_cycle(edges)
    if cycle is not None:
        first = edges.get((cycle[0], cycle[1]), ["?"])[0]
        line = 1
        if ":" in first:
            try:
                line = int(first.split(" ")[0].rsplit(":", 1)[1])
            except ValueError:
                pass
        yield Finding(
            rule="JL022",
            path=mod.path,
            line=line,
            context="<module>",
            detail="lock-order cycle " + " -> ".join(cycle),
            message=(
                "lock-order cycle within this module: "
                + " -> ".join(cycle)
                + " — two threads taking the locks in opposite orders "
                "deadlock. Break the cycle (acquire in one global "
                "order, or drop the lock before the cross call); the "
                "checked-in order lives in analysis/lockorder.json."
            ),
        )


def rule_jl023(mod: ModuleInfo) -> Iterator[Finding]:
    """JL023: unsupervised thread — ``threading.Thread(...)`` with no
    ``name=`` (anonymous in stack dumps, watchdog output, and the
    lock-witness acquisition records), or a thread-creating class with
    no shutdown path: no method that ``.join()``s a thread or sets a
    stop Event. Serving threads must be both identifiable and
    collectable — the PR 9 watchdog and every drain path assume it.
    """
    if not _concurrency_in_scope(mod):
        return
    model = _conc_model(mod)
    sites = []
    for cls in sorted(model.classes.values(), key=lambda c: c.lineno):
        sites.extend(cls.thread_sites)
    sites.extend(model.module_thread_sites)
    for lineno, has_name, target, method in sorted(sites):
        if has_name:
            continue
        tgt = f" (target {target})" if target else ""
        yield Finding(
            rule="JL023",
            path=mod.path,
            line=lineno,
            context=method,
            detail=f"unnamed thread in {method}",
            message=(
                f"threading.Thread created without name= in {method}"
                f"{tgt}: anonymous threads are invisible to watchdog "
                "stacks, the lock witness, and py-spy output — name it "
                "after its role (e.g. name=f\"replica-{i}-dispatch\")."
            ),
        )
    for cls in sorted(model.classes.values(), key=lambda c: c.lineno):
        if not cls.thread_sites:
            continue
        joins = False
        signals = False
        for mm in cls.methods.values():
            for recv, meth, _, _ in mm.local_calls:
                if meth == "join":
                    joins = True
            for attr, owner_tag, meth, _, _ in mm.attr_calls:
                if meth == "join":
                    joins = True
                if meth == "set" and owner_tag == "self" and \
                        cls.attr_kinds.get(attr) == "event":
                    signals = True
        if joins or signals:
            continue
        yield Finding(
            rule="JL023",
            path=mod.path,
            line=cls.lineno,
            context=cls.name,
            detail=f"{cls.name} never joins/stops its threads",
            message=(
                f"{cls.name} creates threads but no method joins them "
                "or sets a stop Event: the thread outlives close()/"
                "drain and is invisible to shutdown supervision. Join "
                "it (or signal a stop Event the worker loop polls) on "
                "the close()/stop() path."
            ),
        )


# ---------------------------------------------------------------------------
# JL024 — wire calls without an explicit timeout in serving code
# ---------------------------------------------------------------------------

# client constructs whose OS-default wait is unbounded (or minutes), and
# the positional index at which their signature accepts the timeout —
# a call is bounded iff it passes timeout= (or fills that slot)
_WIRE_TIMEOUT_SLOT = {
    "HTTPConnection": 2,        # (host, port, timeout=...)
    "HTTPSConnection": 2,
    "urlopen": 2,               # (url, data, timeout=...)
    "create_connection": 1,     # (address, timeout=...)
}
_REQUESTS_VERBS = {
    "get", "post", "put", "delete", "head", "patch", "options", "request",
}


def rule_jl024(mod: ModuleInfo) -> Iterator[Finding]:
    """JL024: an HTTP/socket client call with no explicit ``timeout``
    under ``speakingstyle_tpu/serving/`` — ``HTTPConnection``/
    ``HTTPSConnection``, ``urlopen``, ``requests.<verb>``, or
    ``socket.create_connection`` relying on OS defaults.

    The cluster tier made the serving tree a wire client: dispatches,
    heartbeats, registration, and adoption probes all cross a host
    boundary, and a TCP connect/read with no timeout blocks for however
    long the kernel feels like (minutes on an unroutable peer, forever
    on a silent one). Every lease, breaker, and hedge budget in the
    control plane assumes wire attempts FAIL in bounded time — one
    timeout-less call re-opens the unbounded-wait hole JL013 closed for
    futures and queues. ``socket.setdefaulttimeout`` does not satisfy
    the rule: it is process-global, invisible at the call site, and one
    import can silently reset it.
    """
    p = mod.path.replace("\\", "/")
    if "speakingstyle_tpu/serving/" not in p:
        return
    for node in mod.walk():
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted(node.func) or ""
        leaf = dotted.rsplit(".", 1)[-1]
        slot = None
        if leaf in _WIRE_TIMEOUT_SLOT:
            # create_connection only as socket's (a local helper named
            # create_connection is not a wire primitive)
            if leaf == "create_connection" and not dotted.startswith(
                    ("socket.", "create_connection")):
                continue
            slot = _WIRE_TIMEOUT_SLOT[leaf]
        elif dotted.startswith("requests.") and leaf in _REQUESTS_VERBS:
            slot = None   # requests' timeout is keyword-only in practice
        else:
            continue
        if any(kw.arg == "timeout" for kw in node.keywords):
            continue
        if slot is not None and len(node.args) > slot:
            continue   # the timeout slot is filled positionally
        fn = mod.enclosing_function(node)
        qual = mod.qualname(fn or mod.tree)
        yield Finding(
            rule="JL024",
            path=mod.path,
            line=node.lineno,
            context=qual,
            detail=f"{dotted}(...) with no explicit timeout",
            message=(
                f"`{dotted}(...)` in serving code ({qual}) has no "
                "explicit timeout: a partitioned or silent peer then "
                "blocks this thread past every lease/breaker/hedge "
                "budget (the OS default is minutes to forever). Pass "
                "timeout= at the call site — derive it from the "
                "request class's deadline budget for dispatches, or "
                "cluster.connect_timeout_s for control-plane calls."
            ),
        )


_DTYPE_CTORS = frozenset((
    "float32", "bfloat16", "float16", "float64", "int8", "int4",
))


def _is_weight_tree(node) -> bool:
    """A params/variables tree by name: ``params``/``variables``, a
    ``*_params``/``*_variables`` local, or an attribute chain ending in
    one (``state.params``, ``self.variables``)."""
    if isinstance(node, ast.Attribute):
        name = node.attr
    elif isinstance(node, ast.Name):
        name = node.id
    else:
        return False
    return name in ("params", "variables") or name.endswith(
        ("_params", "_variables")
    )


def rule_jl025(mod: ModuleInfo) -> Iterator[Finding]:
    """JL025: a precision cast of a weight tree outside the sanctioned
    ``cast_params`` helper — ``<tree>.astype(...)``, a
    ``jnp.float32(<tree>)``-style dtype constructor, or a
    ``tree_map(lambda x: x.astype(...), <tree>)`` over a
    params/variables tree anywhere in ``speakingstyle_tpu/`` except
    ``parallel/registry.py``.

    Precision is a lattice axis, not a local convenience: the registry's
    cache key, the ProgramCard rows, the BufferPool dtypes, and the tier
    gates all key on which precision a param tree carries. A cast done
    inline at a call site produces weights the choke point never saw —
    a program compiles and serves at a precision no canary gated and no
    card records, which is exactly the same-bucket-different-precision
    blindness the tier door exists to close. All weight-tree casts flow
    through ``parallel/registry.py``'s ``cast_params`` (bf16 cast,
    int8 per-channel quant) / ``dequant_params`` (in-program f32 read).
    """
    p = mod.path.replace("\\", "/")
    if "speakingstyle_tpu/" not in p or p.endswith("parallel/registry.py"):
        return
    for node in mod.walk():
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted(node.func) or ""
        leaf = dotted.rsplit(".", 1)[-1]
        bad = None
        if (isinstance(node.func, ast.Attribute)
                and node.func.attr == "astype"
                and _is_weight_tree(node.func.value)):
            bad = f"{_dotted(node.func.value)}.astype(...)"
        elif (leaf in _DTYPE_CTORS
                and dotted.startswith(("jnp.", "jax.numpy.", "np.", "numpy."))
                and node.args and _is_weight_tree(node.args[0])):
            bad = f"{dotted}({_dotted(node.args[0])})"
        elif leaf in ("tree_map", "map") and dotted.startswith(
                ("jax.", "tree_map", "tree.")):
            # tree_map(lambda x: x.astype(...), params): the cast hides
            # in the mapped lambda, the tree names the weights
            if not any(_is_weight_tree(a) for a in node.args[1:]):
                continue
            fn_arg = node.args[0] if node.args else None
            if not isinstance(fn_arg, ast.Lambda):
                continue
            for inner in ast.walk(fn_arg.body):
                if not isinstance(inner, ast.Call):
                    continue
                idotted = _dotted(inner.func) or ""
                ileaf = idotted.rsplit(".", 1)[-1]
                if (isinstance(inner.func, ast.Attribute)
                        and inner.func.attr == "astype") or (
                        ileaf in _DTYPE_CTORS and idotted.startswith(
                            ("jnp.", "jax.numpy.", "np.", "numpy."))):
                    bad = f"{dotted}(lambda: ...{ileaf}(...), <weights>)"
                    break
        if bad is None:
            continue
        fn = mod.enclosing_function(node)
        qual = mod.qualname(fn or mod.tree)
        yield Finding(
            rule="JL025",
            path=mod.path,
            line=node.lineno,
            context=qual,
            detail=f"out-of-band weight-tree cast: {bad}",
            message=(
                f"`{bad}` in {qual} casts a weight tree outside the "
                "sanctioned helper: the registry cache key, ProgramCards, "
                "and tier canary gates never see this precision, so a "
                "program can serve quantized/cast weights no gate "
                "approved. Route the cast through cast_params() in "
                "parallel/registry.py (dequant_params for in-program "
                "int8 reads)."
            ),
        )


# ---------------------------------------------------------------------------
# JL026 — label-cardinality bombs at metric registration sites
# ---------------------------------------------------------------------------

_JL026_METHODS = ("counter", "gauge", "histogram")

# terminal identifiers (variable / attribute / subscript-key names) that
# carry per-request identity — each distinct value mints a new series
_JL026_PER_REQUEST = (
    "req_id", "request_id", "trace_id", "span_id", "parent_span_id",
    "idempotency_key", "idem_key", "utterance_id", "session_id",
    "correlation_id", "uuid", "text", "utterance",
)


def _jl026_per_request_ident(node) -> Optional[str]:
    """The terminal identifier of an expression, when it names
    per-request identity (``req_id``, ``r.trace_id``,
    ``payload["text"]``, ...)."""
    if isinstance(node, ast.Name):
        name = node.id
    elif isinstance(node, ast.Attribute):
        name = node.attr
    elif (isinstance(node, ast.Subscript)
            and isinstance(node.slice, ast.Constant)
            and isinstance(node.slice.value, str)):
        name = node.slice.value
    else:
        return None
    low = name.lower()
    for pat in _JL026_PER_REQUEST:
        if low == pat or low.endswith("_" + pat):
            return name
    return None


def rule_jl026(mod: ModuleInfo) -> Iterator[Finding]:
    """JL026: label-cardinality bomb — per-request identity (req_id,
    trace_id, idempotency keys, raw text, ...) flowing into a metric
    NAME or a label VALUE at a ``registry.counter/gauge/histogram``
    call site under ``speakingstyle_tpu/serving/`` or ``obs/``.

    A metric family costs memory per distinct (name, labels) identity,
    FOREVER: counters never expire, every /metrics scrape renders every
    series, and the fleet federation layer (obs/registry.merge_states)
    multiplies the page across replicas. A label whose value is
    per-request — ``labels={"req": req_id}``, a trace id interpolated
    into the metric name — therefore allocates one immortal series per
    request: memory grows linearly with traffic, scrape latency follows,
    and the observability plane becomes the outage. Per-request identity
    belongs on trace spans (bounded ring, obs/trace.py) and JSONL events
    (append-only, rotated), never on metric labels; labels stay bounded
    vocabularies (class, replica, reason, bucket). The rule keys on
    identifier NAMES flowing into the call site, so bounded dynamic
    labels (``{"class": klass}``, ``{"replica": rid}``) stay clean;
    genuinely bounded values with unfortunate names get
    ``# jaxlint: disable=JL026 reason=...``.
    """
    p = mod.path.replace("\\", "/")
    if not ("speakingstyle_tpu/serving/" in p
            or "speakingstyle_tpu/obs/" in p):
        return
    for node in mod.walk():
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _JL026_METHODS):
            continue
        # receiver must look like a metrics registry (self.registry,
        # self._registry, registry, reg) — lexical, like every rule here
        recv = (_dotted(node.func.value) or "").rsplit(".", 1)[-1]
        if "registry" not in recv.lower() and recv != "reg":
            continue
        name_expr = node.args[0] if node.args else None
        labels_expr = None
        for kw in node.keywords:
            if kw.arg == "name":
                name_expr = kw.value
            elif kw.arg == "labels":
                labels_expr = kw.value
        hits: List[Tuple[str, str]] = []
        if name_expr is not None and not isinstance(name_expr, ast.Constant):
            # dynamic name: flag when per-request identity feeds it
            # (f-string pieces, concat operands, or the variable itself)
            for sub in ast.walk(name_expr):
                ident = _jl026_per_request_ident(sub)
                if ident is not None:
                    hits.append(("the metric name", ident))
                    break
        if isinstance(labels_expr, ast.Dict):
            for key, val in zip(labels_expr.keys, labels_expr.values):
                for sub in ast.walk(val):
                    ident = _jl026_per_request_ident(sub)
                    if ident is not None:
                        label = (key.value if isinstance(key, ast.Constant)
                                 else _dotted(key) or "?")
                        hits.append((f"label {label!r}", ident))
                        break
        for where, ident in hits:
            fn = mod.enclosing_function(node)
            qual = mod.qualname(fn or mod.tree)
            yield Finding(
                rule="JL026",
                path=mod.path,
                line=node.lineno,
                context=qual,
                detail=f"per-request `{ident}` in {where}",
                message=(
                    f"`{node.func.attr}(...)` in {qual} puts per-request "
                    f"`{ident}` into {where}: each distinct value mints an "
                    "immortal time series, so the /metrics page (and every "
                    "federation merge over it) grows with traffic forever. "
                    "Put per-request identity on trace spans or JSONL "
                    "events; keep metric labels a bounded vocabulary "
                    "(class, replica, reason, bucket)."
                ),
            )


# ---------------------------------------------------------------------------
# JL027 — audio bytes leaving serving code without the quality choke point
# ---------------------------------------------------------------------------

# terminal identifiers whose ``.tobytes()`` is audio leaving the process
_JL027_AUDIO_TERMINALS = ("wav", "pcm", "audio", "chunk", "piece")

# bare-call leaves that count as validator evidence
_JL027_VALIDATORS = (
    "validate_wav", "check_wav", "check_result", "quality_check",
)


def _jl027_is_emission(node: ast.Call) -> Optional[str]:
    """What kind of audio-emission site a call is, or None.

    Three shapes: ``wav_bytes(...)`` (the RIFF container),
    ``<x>.astype(np.int16 | "int16")`` (the float->PCM conversion every
    audio path performs exactly once), and ``<audio-ish>.tobytes()``
    where the receiver's TERMINAL identifier names audio (``wav``,
    ``chunk.tobytes()`` — terminal-only, so ``np.asarray(wav,
    np.int16).tobytes()`` inside the sanctioned container helper and a
    generic ``a.tobytes()`` stay clean)."""
    func = node.func
    leaf = (func.attr if isinstance(func, ast.Attribute)
            else func.id if isinstance(func, ast.Name) else "")
    if leaf == "wav_bytes":
        return "wav_bytes(...)"
    if leaf == "astype" and node.args:
        a = node.args[0]
        if ((isinstance(a, ast.Attribute) and a.attr == "int16")
                or (isinstance(a, ast.Name) and a.id == "int16")
                or (isinstance(a, ast.Constant) and a.value == "int16")):
            return ".astype(int16)"
    if leaf == "tobytes" and isinstance(func, ast.Attribute):
        recv = func.value
        name = (recv.id if isinstance(recv, ast.Name)
                else recv.attr if isinstance(recv, ast.Attribute) else "")
        low = name.lower()
        for t in _JL027_AUDIO_TERMINALS:
            if low == t or low.endswith("_" + t) or low.startswith(t):
                return f"{name}.tobytes()"
    return None


def _jl027_is_evidence(node: ast.Call) -> bool:
    """A call that passes audio through the quality choke point:
    a dotted call through something named ``quality`` whose leaf
    checks/validates (``self.quality.check``, ``outer.quality_gate
    .check_result``, the Stitcher's ``self.quality_check(p)``), or a
    bare validator call (``validate_wav(...)``)."""
    func = node.func
    if isinstance(func, ast.Attribute):
        dotted = _dotted(func).lower()
        leaf = func.attr.lower()
        return "quality" in dotted and (
            "check" in leaf or "validate" in leaf
        )
    if isinstance(func, ast.Name):
        return func.id in _JL027_VALIDATORS
    return False


def rule_jl027(mod: ModuleInfo) -> Iterator[Finding]:
    """JL027: audio bytes leaving serving code without passing the
    quality choke point (obs/quality.py).

    The quality observability plane only works if EVERY wav crosses the
    validator exactly where it is produced or served: the engine's batch
    and streaming collect paths, the long-form stitcher, and the HTTP
    boundary all call ``QualityGate.check``/``check_result`` (or the
    stitcher's injected ``quality_check``) before bytes move on. A new
    audio path that converts to int16 PCM, wraps a RIFF container
    (``wav_bytes``), or serializes an audio buffer (``wav.tobytes()``)
    WITHOUT validator evidence in the same function ships garbage the
    whole plane — counters, quality SLO burn, pinned traces, paging —
    is blind to. The rule is lexical per enclosing function: any
    quality-check call in the function (or an enclosing one) sanctions
    its emissions; genuinely non-audio int16 conversions get
    ``# jaxlint: disable=JL027 reason=...``.
    """
    p = mod.path.replace("\\", "/")
    if "speakingstyle_tpu/serving/" not in p:
        return
    evidence_fns = set()
    for node in mod.walk():
        if isinstance(node, ast.Call) and _jl027_is_evidence(node):
            fn = mod.enclosing_function(node)
            if fn is not None:
                evidence_fns.add(fn)
    for node in mod.walk():
        if not isinstance(node, ast.Call):
            continue
        what = _jl027_is_emission(node)
        if what is None:
            continue
        # sanctioned if this function — or any function it is nested
        # inside (a helper closure emits what the handler validated) —
        # carries validator evidence
        cur = mod.enclosing_function(node)
        sanctioned = False
        probe = cur
        while probe is not None:
            if probe in evidence_fns:
                sanctioned = True
                break
            probe = mod.enclosing_function(probe)
        if sanctioned:
            continue
        qual = mod.qualname(cur or mod.tree)
        yield Finding(
            rule="JL027",
            path=mod.path,
            line=node.lineno,
            context=qual,
            detail=f"unvalidated audio emission {what}",
            message=(
                f"`{what}` in {qual} emits audio bytes without passing "
                "the quality choke point: no "
                "`QualityGate.check/check_result`, `validate_wav`, or "
                "injected `quality_check` call in this function. Every "
                "wav must cross obs/quality.py where it is produced — "
                "otherwise the validators, the quality SLO stream, and "
                "the golden-probe drill are blind to this path. Route "
                "the buffer through the engine/server gate (or call "
                "validate_wav directly) before serializing."
            ),
        )


RULES = {
    "JL001": rule_jl001,
    "JL002": rule_jl002,
    "JL003": rule_jl003,
    "JL004": rule_jl004,
    "JL005": rule_jl005,
    "JL006": rule_jl006,
    "JL007": rule_jl007,
    "JL008": rule_jl008,
    "JL009": rule_jl009,
    "JL010": rule_jl010,
    "JL011": rule_jl011,
    "JL012": rule_jl012,
    "JL013": rule_jl013,
    "JL014": rule_jl014,
    "JL015": rule_jl015,
    "JL016": rule_jl016,
    "JL017": rule_jl017,
    "JL018": rule_jl018,
    "JL019": rule_jl019,
    "JL020": rule_jl020,
    "JL021": rule_jl021,
    "JL022": rule_jl022,
    "JL023": rule_jl023,
    "JL024": rule_jl024,
    "JL025": rule_jl025,
    "JL026": rule_jl026,
    "JL027": rule_jl027,
}
