"""jaxlint + runtime-contract tests (tier-1 regression gate).

Three layers:
  1. fixture tests — every JL rule has positive (fires) and negative
     (stays silent) snippets, linted in-memory via ``lint_source``;
  2. suppression + baseline mechanics — inline disables, skip-file, and
     the bidirectional baseline compare;
  3. the real gate — the package is clean modulo the committed baseline
     (fails loudly when either the code or the baseline drifts), and the
     CLI exit codes match the contract in ``scripts/lint_jax.py``.
"""

import textwrap

import numpy as np
import pytest

from speakingstyle_tpu.analysis import cli, contracts, linter


def _codes(source, path="speakingstyle_tpu/fake.py"):
    return sorted({f.rule for f in linter.lint_source(
        textwrap.dedent(source), path
    )})


# ---------------------------------------------------------------------------
# JL001 — trace-unsafe control flow
# ---------------------------------------------------------------------------


def test_jl001_positive_if_on_traced_param():
    assert "JL001" in _codes("""
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
    """)


def test_jl001_positive_nn_module_call():
    assert "JL001" in _codes("""
        import flax.linen as nn

        class Layer(nn.Module):
            def __call__(self, x):
                while x < 0:
                    x = x + 1
                return x
    """)


def test_jl001_negative_shape_branch_and_untraced():
    # metadata branches and plain functions are trace-safe
    assert "JL001" not in _codes("""
        import jax

        @jax.jit
        def f(x):
            if x.shape[0] > 2:
                return x[:2]
            return x

        def g(x):
            if x > 0:
                return x
            return -x
    """)


# ---------------------------------------------------------------------------
# JL002 — numpy on jax arrays
# ---------------------------------------------------------------------------

_JL002_SRC = """
    import numpy as np
    import jax.numpy as jnp

    def f():
        y = jnp.ones((3,))
        return np.sum(y)
"""


def test_jl002_positive_np_on_jax_array():
    assert "JL002" in _codes(_JL002_SRC)


def test_jl002_negative_tests_are_exempt():
    assert _codes(_JL002_SRC, path="tests/test_fake.py") == []


def test_jl002_negative_np_on_host_data():
    assert "JL002" not in _codes("""
        import numpy as np
        import jax.numpy as jnp

        def f(host_list):
            y = jnp.ones((3,))
            z = jnp.sum(y)
            return np.sum(host_list), z
    """)


# ---------------------------------------------------------------------------
# JL003 — donation / static hashability
# ---------------------------------------------------------------------------


def test_jl003_positive_missing_donation():
    assert "JL003" in _codes("""
        import jax

        def step(state, batch):
            new_state = state.replace(step=state.step + 1)
            return new_state

        step = jax.jit(step)
    """)


def test_jl003_negative_donated():
    assert "JL003" not in _codes("""
        import jax

        def step(state, batch):
            new_state = state.replace(step=state.step + 1)
            return new_state

        step = jax.jit(step, donate_argnums=(0,))
    """)


def test_jl003_positive_unhashable_static():
    assert "JL003" in _codes("""
        import jax

        def f(x, shapes):
            return x

        g = jax.jit(f, static_argnums=(1,))

        def run(x):
            return g(x, [1, 2])
    """)


# ---------------------------------------------------------------------------
# JL004 — host sync in training loops
# ---------------------------------------------------------------------------

_JL004_SRC = """
    def loop(batches):
        total = 0.0
        for b in batches:
            total += b.loss.item()
        return total
"""


def test_jl004_positive_item_in_training_loop():
    assert "JL004" in _codes(
        _JL004_SRC, path="speakingstyle_tpu/training/fake.py"
    )


def test_jl004_negative_outside_training():
    # same pattern outside training/ is out of scope for this rule
    assert "JL004" not in _codes(
        _JL004_SRC, path="speakingstyle_tpu/ops/fake.py"
    )


def test_jl004_negative_sync_outside_loop():
    assert "JL004" not in _codes("""
        def summarize(final_loss):
            return float(final_loss)
    """, path="speakingstyle_tpu/training/fake.py")


# ---------------------------------------------------------------------------
# JL005 — recompilation hazards
# ---------------------------------------------------------------------------


def test_jl005_positive_config_in_signature():
    assert "JL005" in _codes("""
        import jax

        @jax.jit
        def f(x, cfg):
            return x * cfg.scale
    """)


def test_jl005_positive_dict_param_and_scalar_default():
    codes = linter.lint_source(textwrap.dedent("""
        import jax
        from typing import Dict

        def f(batch: Dict, scale: float = 1.0):
            return batch

        g = jax.jit(f)
    """), "speakingstyle_tpu/fake.py")
    details = {c.detail for c in codes if c.rule == "JL005"}
    assert any("Dict-typed" in d for d in details)
    assert any("scalar param" in d for d in details)


def test_jl005_positive_jit_in_loop():
    assert "JL005" in _codes("""
        import jax

        def main(fns):
            outs = []
            for f in fns:
                outs.append(jax.jit(f))
            return outs
    """)


def test_jl005_negative_static_config():
    assert "JL005" not in _codes("""
        import jax
        import functools

        @functools.partial(jax.jit, static_argnames=("cfg",))
        def f(x, cfg):
            return x * cfg.scale
    """)


# ---------------------------------------------------------------------------
# JL006 — PRNG key reuse
# ---------------------------------------------------------------------------


def test_jl006_positive_key_reuse():
    assert "JL006" in _codes("""
        import jax

        def f(rng):
            a = jax.random.normal(rng, (2,))
            b = jax.random.normal(rng, (2,))
            return a + b
    """)


def test_jl006_positive_key_in_loop():
    assert "JL006" in _codes("""
        import jax

        def f(rng, n):
            out = 0.0
            for _ in range(n):
                out = out + jax.random.normal(rng, (2,))
            return out
    """)


def test_jl006_positive_constant_key_in_traced_context():
    assert "JL006" in _codes("""
        import jax

        @jax.jit
        def f(x):
            k = jax.random.PRNGKey(0)
            return x + jax.random.normal(k, x.shape)
    """)


def test_jl006_negative_split_before_use():
    assert "JL006" not in _codes("""
        import jax

        def f(rng):
            k1, k2 = jax.random.split(rng)
            a = jax.random.normal(k1, (2,))
            b = jax.random.normal(k2, (2,))
            return a + b
    """)


def test_jl006_negative_flax_rngs_dict_idiom():
    # .init/.apply fold the collection name into the key: not reuse
    assert "JL006" not in _codes("""
        def f(model, rng, x):
            return model.init({"params": rng, "dropout": rng}, x)
    """)


# ---------------------------------------------------------------------------
# JL007 — swallowed exceptions
# ---------------------------------------------------------------------------


def test_jl007_positive_broad_except_pass():
    assert "JL007" in _codes("""
        def f(path):
            try:
                return open(path).read()
            except Exception:
                pass
    """)


def test_jl007_positive_bare_except_continue():
    assert "JL007" in _codes("""
        def f(paths):
            out = []
            for p in paths:
                try:
                    out.append(open(p).read())
                except:
                    continue
            return out
    """)


def test_jl007_positive_silent_fallback_value():
    # `except Exception: x = None` swallows just as silently as pass
    assert "JL007" in _codes("""
        def f(raw):
            try:
                data = parse(raw)
            except Exception:
                data = None
            return data
    """)


def test_jl007_negative_specific_exception():
    assert "JL007" not in _codes("""
        def f():
            try:
                import tensorboardX
            except ImportError:
                pass
    """)


def test_jl007_negative_logged_or_reraised():
    assert "JL007" not in _codes("""
        def f(path):
            try:
                return open(path).read()
            except Exception as e:
                print(f"read failed: {e}")
                raise
    """)


def test_jl007_negative_error_is_used():
    # re-packaging the error (e.g. the prefetcher handing it to the
    # consumer thread) is handling, not swallowing
    assert "JL007" not in _codes("""
        def f(q, fn):
            try:
                q.put(fn())
            except Exception as e:
                q.put(e)
    """)


def test_jl007_negative_outside_package():
    assert "JL007" not in _codes("""
        def f(path):
            try:
                return open(path).read()
            except Exception:
                pass
    """, path="tests/fake.py")


# ---------------------------------------------------------------------------
# JL008 — compile in hot path
# ---------------------------------------------------------------------------


def test_jl008_positive_jit_in_loop():
    assert "JL008" in _codes("""
        import jax

        def sweep(variants, x):
            outs = []
            for v in variants:
                f = jax.jit(lambda y: y * v)
                outs.append(f(x))
            return outs
    """)


def test_jl008_positive_aot_chain_in_loop():
    assert "JL008" in _codes("""
        import jax

        def build(fns, args):
            return [jax.jit(f).lower(*args).compile() for f in fns]

        def rebuild_each_step(fn, batches):
            for b in batches:
                exe = jax.jit(fn).lower(b).compile()
                exe(b)
    """)


def test_jl008_positive_jit_in_request_handler():
    # http.server-style do_POST and handle_* names are hot request paths
    assert "JL008" in _codes("""
        import jax

        class Handler:
            def do_POST(self):
                f = jax.jit(self.model_fn)
                return f(self.payload)
    """)
    assert "JL008" in _codes("""
        import jax

        def handle_synthesis(model_fn, payload):
            return jax.jit(model_fn)(payload)
    """)


def test_jl008_negative_module_level_and_startup():
    assert "JL008" not in _codes("""
        import jax

        step = jax.jit(lambda s, b: s + b)

        def serve(batches):
            for b in batches:
                step(1, b)
    """)


def test_jl008_negative_precompile_function_exempt():
    # the sanctioned AOT startup pattern (serving/engine.py)
    assert "JL008" not in _codes("""
        import jax

        def precompile(fn, lattice):
            exes = {}
            for point in lattice:
                exes[point] = jax.jit(fn).lower(point).compile()
            return exes

        def warmup_all(fn, shapes):
            return [jax.jit(fn).lower(s).compile() for s in shapes]
    """)


def test_jl008_negative_re_compile_untouched():
    # only the .lower().compile() AOT chain counts, not other .compile()s
    assert "JL008" not in _codes("""
        import re

        def scan(lines, patterns):
            for p in patterns:
                rx = re.compile(p)
                for ln in lines:
                    rx.match(ln)
    """)


# ---------------------------------------------------------------------------
# JL009 — wall clock used for durations
# ---------------------------------------------------------------------------


def test_jl009_positive_time_time_subtraction():
    assert "JL009" in _codes("""
        import time

        def measure(fn):
            t0 = time.time()
            fn()
            return time.time() - t0
    """)


def test_jl009_positive_from_import_and_alias():
    assert "JL009" in _codes("""
        from time import time

        def measure(fn):
            start = time()
            fn()
            return time() - start
    """)


def test_jl009_positive_stamp_name_subtracted_later():
    assert "JL009" in _codes("""
        import time

        def loop(items):
            began = time.time()
            for it in items:
                handle(it)
            report(elapsed=time.monotonic() - began)
    """)


def test_jl009_negative_monotonic_and_perf_counter():
    assert "JL009" not in _codes("""
        import time

        def measure(fn):
            t0 = time.monotonic()
            fn()
            d1 = time.monotonic() - t0
            t1 = time.perf_counter()
            fn()
            return d1 + (time.perf_counter() - t1)
    """)


def test_jl009_negative_timestamp_only_use():
    # wall time as a *timestamp* (never subtracted) is the sanctioned use
    assert "JL009" not in _codes("""
        import time

        def record(log, event):
            log.emit({"ts": time.time(), "event": event})
    """)


# ---------------------------------------------------------------------------
# JL010 — jitted-call timing without a device sync
# ---------------------------------------------------------------------------


def test_jl010_positive_unsynced_jit_timing():
    assert "JL010" in _codes("""
        import time
        import jax

        def bench(f, x):
            g = jax.jit(f)
            t0 = time.monotonic()
            y = g(x)
            return time.monotonic() - t0
    """)


def test_jl010_positive_aot_compiled_callable():
    assert "JL010" in _codes("""
        import time
        import jax

        def bench(f, x):
            compiled = jax.jit(f).lower(x).compile()
            t0 = time.perf_counter()
            for _ in range(10):
                y = compiled(x)
            dt = time.perf_counter() - t0
            return dt
    """)


def test_jl010_negative_block_until_ready_in_region():
    assert "JL010" not in _codes("""
        import time
        import jax

        def bench(f, x):
            g = jax.jit(f)
            t0 = time.monotonic()
            y = g(x)
            jax.block_until_ready(y)
            return time.monotonic() - t0
    """)


def test_jl010_negative_device_read_in_region():
    # the repo's sanctioned sync idiom: an explicit D2H scalar read
    assert "JL010" not in _codes("""
        import time
        import jax

        def bench(f, x):
            g = jax.jit(f)
            t0 = time.perf_counter()
            for _ in range(10):
                y = g(x)
            float(y)
            return time.perf_counter() - t0
    """)


def test_jl010_negative_non_jitted_timing():
    assert "JL010" not in _codes("""
        import time

        def bench(load):
            t0 = time.monotonic()
            load()
            return time.monotonic() - t0
    """)


# ---------------------------------------------------------------------------
# JL011 — unbounded queues in serving code
# ---------------------------------------------------------------------------

_SERVING_PATH = "speakingstyle_tpu/serving/fake.py"


def test_jl011_positive_unbounded_queue_in_serving():
    assert "JL011" in _codes("""
        import queue

        class Admission:
            def __init__(self):
                self.pending = queue.Queue()
    """, path=_SERVING_PATH)


def test_jl011_positive_zero_maxsize_and_simplequeue():
    src = """
        import queue

        def build():
            a = queue.Queue(maxsize=0)   # stdlib: 0 = infinite
            b = queue.SimpleQueue()      # cannot be bounded at all
            return a, b
    """
    codes = sorted({
        f.detail for f in linter.lint_source(
            textwrap.dedent(src), _SERVING_PATH
        ) if f.rule == "JL011"
    })
    assert len(codes) == 2


def test_jl011_negative_bounded_queue():
    assert "JL011" not in _codes("""
        import queue

        def build(depth):
            a = queue.Queue(maxsize=depth)
            b = queue.PriorityQueue(16)
            return a, b
    """, path=_SERVING_PATH)


def test_jl011_negative_outside_serving():
    # scoped: backpressure is a serving contract; elsewhere an unbounded
    # queue can be a deliberate choice
    assert "JL011" not in _codes("""
        import queue

        q = queue.Queue()
    """, path="speakingstyle_tpu/training/fake.py")


# ---------------------------------------------------------------------------
# JL012 — unbounded caches in serving code
# ---------------------------------------------------------------------------


def test_jl012_positive_dict_cache_in_serving():
    assert "JL012" in _codes("""
        class Frontend:
            def __init__(self):
                self._mel_cache = {}
    """, path=_SERVING_PATH)


def test_jl012_positive_annotated_dict_cache():
    assert "JL012" in _codes("""
        from typing import Dict

        class Frontend:
            def __init__(self):
                self.style_cache: Dict[str, bytes] = dict()
    """, path=_SERVING_PATH)


def test_jl012_positive_lru_cache_maxsize_none_and_functools_cache():
    src = """
        import functools
        from functools import lru_cache

        @lru_cache(maxsize=None)
        def embed(key):
            return key

        @functools.cache
        def lookup(key):
            return key
    """
    details = sorted({
        f.detail for f in linter.lint_source(
            textwrap.dedent(src), _SERVING_PATH
        ) if f.rule == "JL012"
    })
    assert len(details) == 2


def test_jl012_negative_bounded_lru_and_non_cache_dicts():
    # bare lru_cache() keeps the stdlib's bounded default of 128;
    # non-cache-named dicts (routing tables, program maps) are state,
    # not caches — both stay silent
    assert "JL012" not in _codes("""
        from functools import lru_cache

        @lru_cache(maxsize=64)
        def embed(key):
            return key

        @lru_cache()
        def small(key):
            return key

        class Engine:
            def __init__(self):
                self._programs = {}
                self.routes = dict()
    """, path=_SERVING_PATH)


def test_jl012_negative_outside_serving():
    # scoped like JL011: outside serving/ an unbounded memo can be a
    # deliberate choice (e.g. a per-process constant table)
    assert "JL012" not in _codes("""
        class Frontend:
            def __init__(self):
                self._mel_cache = {}
    """, path="speakingstyle_tpu/training/fake.py")


# ---------------------------------------------------------------------------
# JL013 — unbounded blocking waits in serving code
# ---------------------------------------------------------------------------


def test_jl013_positive_bare_result_and_get():
    src = """
        def serve(future, q):
            x = future.result()
            y = q.get()
            return x, y
    """
    details = sorted({
        f.detail for f in linter.lint_source(
            textwrap.dedent(src), _SERVING_PATH
        ) if f.rule == "JL013"
    })
    assert len(details) == 2


def test_jl013_negative_timeout_and_dict_get():
    # timeout= (or a positional deadline) bounds the wait; dict.get(key)
    # carries a positional argument and is not a blocking wait at all
    assert "JL013" not in _codes("""
        def serve(future, q, table):
            x = future.result(timeout=2.5)
            y = q.get(timeout=0.1)
            z = future.result(30)
            return x, y, z, table.get("k"), table.get("k", None)
    """, path=_SERVING_PATH)


def test_jl013_negative_outside_serving():
    # scoped: a training-side collective or a test helper may block
    # deliberately (the process has no request deadline to honor)
    assert "JL013" not in _codes("""
        def gather(future, q):
            return future.result(), q.get()
    """, path="speakingstyle_tpu/training/fake.py")


# ---------------------------------------------------------------------------
# JL014 — hard single-device pinning in training/data code
# ---------------------------------------------------------------------------


def test_jl014_positive_direct_and_via_name():
    src = """
        import jax

        def load(batch):
            dev = jax.local_devices()[0]
            a = jax.device_put(batch, jax.devices()[0])
            b = jax.device_put(batch, device=dev)
            return a, b
    """
    details = sorted({
        f.detail for f in linter.lint_source(
            textwrap.dedent(src), "speakingstyle_tpu/training/fake.py"
        ) if f.rule == "JL014"
    })
    assert details == [
        "device_put pinned to dev",
        "device_put pinned to jax.devices()[...]",
    ]


def test_jl014_positive_under_data_path():
    assert "JL014" in _codes("""
        import jax

        def put(v):
            return jax.device_put(v, jax.devices()[0])
    """, path="speakingstyle_tpu/data/fake.py")


def test_jl014_negative_sharding_device_put():
    # the contract: device_put against a NamedSharding (or no device)
    assert "JL014" not in _codes("""
        import jax

        def put(v, sharding):
            return {"a": jax.device_put(v, sharding), "b": jax.device_put(v)}
    """, path="speakingstyle_tpu/data/fake.py")


def test_jl014_negative_outside_training_and_data():
    # scoped: ops/ kernels and obs/ probes legitimately address one device
    assert "JL014" not in _codes("""
        import jax

        def probe(v):
            return jax.device_put(v, jax.devices()[0])
    """, path="speakingstyle_tpu/ops/fake.py")


# ---------------------------------------------------------------------------
# JL015 — fresh ndarray allocation in the serving hot path
# ---------------------------------------------------------------------------


def test_jl015_positive_alloc_in_dispatch_loop_and_handler():
    src = """
        import numpy as np

        def _dispatch(batch):
            out = []
            for req in batch:
                buf = np.zeros((4, 16), np.float32)
                out.append(np.pad(req, (0, 4)))
            return np.concatenate(out)
    """
    details = sorted({
        f.detail for f in linter.lint_source(
            textwrap.dedent(src), _SERVING_PATH
        ) if f.rule == "JL015"
    })
    assert details == [
        "np.concatenate in dispatch/handler function",
        "np.pad in loop",
        "np.zeros in loop",
    ]


def test_jl015_negative_precompile_and_pool_lease():
    # startup allocation is sanctioned; the steady-state idiom leases a
    # pooled buffer and writes in place
    assert "JL015" not in _codes("""
        import numpy as np

        def precompile(lattice):
            for point in lattice:
                np.zeros(point.shape, np.float32)

        def _dispatch(pool, batch, shape):
            with pool.lease(shape) as buf:
                np.copyto(buf[: len(batch)], 1.0)
                return buf
    """, path=_SERVING_PATH)


def test_jl015_negative_outside_serving():
    # a data loader may build fresh arrays per batch; only the serving
    # hot path carries the allocation-free contract
    assert "JL015" not in _codes("""
        import numpy as np

        def _dispatch(batch):
            for b in batch:
                buf = np.zeros((4,), np.float32)
            return buf
    """, path="speakingstyle_tpu/training/fake.py")


# ---------------------------------------------------------------------------
# JL016 — bare time.sleep in serving loops
# ---------------------------------------------------------------------------


def test_jl016_positive_sleep_in_supervision_loop():
    src = """
        import threading
        import time

        def _supervise(self):
            while not self._stop:
                self._sweep()
                time.sleep(0.25)
    """
    found = [
        f for f in linter.lint_source(textwrap.dedent(src), _SERVING_PATH)
        if f.rule == "JL016"
    ]
    assert len(found) == 1
    assert found[0].detail == "time.sleep in loop"
    assert "Event.wait" in found[0].message


def test_jl016_positive_bare_sleep_import_in_for_loop():
    assert "JL016" in _codes("""
        from time import sleep

        def drain(self, replicas):
            for rep in replicas:
                sleep(0.1)
    """, path=_SERVING_PATH)


def test_jl016_negative_stop_aware_waits_and_one_shot_sleep():
    # the sanctioned idioms: Event.wait / Condition.wait as the loop
    # timer, and a one-shot settle sleep outside any loop
    assert "JL016" not in _codes("""
        import threading
        import time

        def _loop(self):
            while not self._stop.wait(self.interval_s):
                self.step()

        def _supervise(self):
            while True:
                with self._cond:
                    self._cond.wait(timeout=0.25)

        def close(self):
            time.sleep(0.06)
    """, path=_SERVING_PATH)


def test_jl016_negative_outside_serving():
    # bench loops and training backoffs may sleep; only serving-side
    # loops carry the stop-aware contract
    assert "JL016" not in _codes("""
        import time

        def poll(self):
            while self.busy():
                time.sleep(0.01)
    """, path="speakingstyle_tpu/training/fake.py")


# ---------------------------------------------------------------------------
# JL017 — non-atomic persistent writes to artifact paths
# ---------------------------------------------------------------------------

_TRAINING_PATH = "speakingstyle_tpu/training/fake.py"


def test_jl017_positive_open_w_on_manifest_path():
    found = [
        f for f in linter.lint_source(textwrap.dedent("""
            import json

            def save_manifest(manifest_path, data):
                with open(manifest_path, "w") as fh:
                    json.dump(data, fh)
        """), _TRAINING_PATH)
        if f.rule == "JL017"
    ]
    assert len(found) == 1
    assert "non-atomic open" in found[0].detail
    assert "os.replace" in found[0].message


def test_jl017_positive_np_save_on_weights_path():
    assert "JL017" in _codes("""
        import numpy as np

        def snapshot(weights_path, arr):
            np.save(weights_path, arr)
    """, path=_SERVING_PATH)


def test_jl017_positive_mode_keyword():
    assert "JL017" in _codes("""
        def write(ckpt_dir):
            fh = open(ckpt_dir + "/state.json", mode="w")
            fh.close()
    """, path=_TRAINING_PATH)


def test_jl017_negative_temp_then_replace():
    # the sanctioned idiom: write a temp sibling, fsync, os.replace —
    # either the temp marker in the path or the rename in scope clears it
    assert "JL017" not in _codes("""
        import json
        import os

        def save_manifest(manifest_path, data):
            tmp = manifest_path + ".tmp"
            with open(tmp, "w") as fh:
                json.dump(data, fh)
                fh.flush()
                os.fsync(fh.fileno())
            os.replace(tmp, manifest_path)
    """, path=_TRAINING_PATH)


def test_jl017_negative_non_artifact_path_and_read_mode():
    # log files and reads are out of scope; only artifact-shaped names
    # (ckpt / manifest / weights / ...) carry the atomicity contract
    assert "JL017" not in _codes("""
        def dump(log_path, ckpt_path):
            open(log_path, "w").close()
            open(ckpt_path).read()
    """, path=_SERVING_PATH)


def test_jl017_negative_outside_training_serving():
    # bench/analysis scratch writes are exempt: the rule polices the
    # persistent-state subtrees only
    assert "JL017" not in _codes("""
        def save(ckpt_path, blob):
            with open(ckpt_path, "w") as fh:
                fh.write(blob)
    """, path="speakingstyle_tpu/analysis/fake.py")


# ---------------------------------------------------------------------------
# JL018 — XLA compilation outside the program registry
# ---------------------------------------------------------------------------


def test_jl018_positive_jit_call_and_decorator():
    found = _codes("""
        import functools
        import jax

        @jax.jit
        def f(x):
            return x

        @functools.partial(jax.jit, static_argnums=(1,))
        def g(x, n):
            return x * n

        h = jax.jit(lambda y: y)
    """, path="speakingstyle_tpu/serving/fake.py")
    assert "JL018" in found


def test_jl018_positive_from_import_and_aot_chain():
    assert "JL018" in _codes("""
        from jax import jit

        def build(fn, args):
            return fn.lower(*args).compile()
    """, path="speakingstyle_tpu/training/fake.py")


def test_jl018_negative_registry_and_out_of_scope():
    src = """
        import jax

        def compile_it(fn):
            return jax.jit(fn)
    """
    # the one sanctioned file
    assert "JL018" not in _codes(
        src, path="speakingstyle_tpu/parallel/registry.py"
    )
    # tests/scripts are fixtures, not production programs; a root-level
    # script is no more enforced than they are
    assert "JL018" not in _codes(src, path="tests/fake.py")
    assert "JL018" not in _codes(src, path="scripts/fake.py")
    assert "JL018" not in _codes(src, path="tool.py")


def test_jl018_negative_precompile_exempt():
    assert "JL018" not in _codes("""
        import jax

        def precompile(fns):
            return [jax.jit(f) for f in fns]
    """, path="speakingstyle_tpu/serving/fake.py")


def test_jl018_jit_program_is_clean_and_recognized_as_tracing():
    # the sanctioned spelling passes JL018 AND keeps the dataflow rules
    # awake: jit_program-wrapped functions are traced contexts (JL001)
    found = _codes("""
        from speakingstyle_tpu.parallel.registry import jit_program

        @jit_program
        def f(x):
            if x > 0:
                return x
            return -x
    """, path="speakingstyle_tpu/serving/fake.py")
    assert "JL018" not in found
    assert "JL001" in found


def test_jl018_tree_baseline_is_zero():
    """The structural invariant the registry migration bought: no file
    in the enforced tree spells jax.jit / .lower().compile() anymore,
    and none may regress into it (JL018 has NO baseline allowance)."""
    findings = [f for f in linter.lint_paths() if f.rule == "JL018"]
    assert findings == [], (
        "JL018 must stay at zero tree findings — route compiles through "
        f"ProgramRegistry/jit_program: {[f.fingerprint for f in findings]}"
    )


# ---------------------------------------------------------------------------
# JL019 — full-utterance accumulation (append-in-loop + concatenate)
# ---------------------------------------------------------------------------


def test_jl019_positive_append_loop_then_concatenate():
    # the concatenate sits AFTER the loop, so JL015's in-loop test never
    # sees it — this is exactly the spelling JL019 exists for
    src = """
        import numpy as np

        def collect(chunks):
            pieces = []
            for c in chunks:
                pieces.append(c.wav)
            return np.concatenate(pieces)
    """
    found = [
        f for f in linter.lint_source(textwrap.dedent(src), _SERVING_PATH)
        if f.rule == "JL019"
    ]
    assert len(found) == 1
    assert found[0].detail == "np.concatenate(pieces) after loop accumulation"


def test_jl019_positive_jnp_and_extend():
    assert "JL019" in _codes("""
        import jax.numpy as jnp

        def gather(windows):
            mels = []
            while windows:
                mels.extend(windows.pop())
            return jnp.concatenate(mels, axis=0)
    """, path=_SERVING_PATH)


def test_jl019_negative_streaming_yield_and_comprehension():
    # the sanctioned shapes: yield pieces as they are produced, or a
    # concatenate over a comprehension/static list (no loop-grown
    # accumulator — small, bounded, not utterance-scale)
    assert "JL019" not in _codes("""
        import numpy as np

        def stream(chunks):
            for c in chunks:
                yield c.wav

        def pack(rows):
            return np.concatenate([r.head for r in rows])
    """, path=_SERVING_PATH)


def test_jl019_negative_scope_and_path():
    # a list grown in ONE function and concatenated in another is not
    # the pattern (the accumulator never coexists with the concat), and
    # non-serving code may accumulate freely
    assert "JL019" not in _codes("""
        import numpy as np

        def grow(chunks):
            pieces = []
            for c in chunks:
                pieces.append(c)
            return pieces

        def join(pieces):
            return np.concatenate(pieces)
    """, path=_SERVING_PATH)
    assert "JL019" not in _codes("""
        import numpy as np

        def collect(chunks):
            pieces = []
            for c in chunks:
                pieces.append(c)
            return np.concatenate(pieces)
    """, path="speakingstyle_tpu/training/fake.py")


def test_jl019_negative_precompile_exempt():
    assert "JL019" not in _codes("""
        import numpy as np

        def precompile(points):
            shapes = []
            for p in points:
                shapes.append(np.zeros(p))
            return np.concatenate(shapes)
    """, path=_SERVING_PATH)


def test_jl019_tree_baseline_is_zero():
    """The long-form subsystem's bounded-memory claim, structurally: no
    serving file accumulates-then-concatenates a full utterance (the
    Stitcher holds one crossfade tail; streaming emits windows)."""
    findings = [f for f in linter.lint_paths() if f.rule == "JL019"]
    assert findings == [], (
        "JL019 must stay at zero tree findings — stream pieces instead "
        f"of rebuilding utterances: {[f.fingerprint for f in findings]}"
    )


# ---------------------------------------------------------------------------
# JL024 — wire calls without an explicit timeout in serving code
# ---------------------------------------------------------------------------


def test_jl024_positive_each_wire_primitive():
    src = """
        import socket
        import urllib.request
        from http.client import HTTPConnection
        import requests

        def register(host, port, url):
            conn = HTTPConnection(host, port)
            page = urllib.request.urlopen(url)
            resp = requests.post(url, json={"ready": True})
            raw = socket.create_connection((host, port))
            return conn, page, resp, raw
    """
    found = [
        f for f in linter.lint_source(textwrap.dedent(src), _SERVING_PATH)
        if f.rule == "JL024"
    ]
    assert len(found) == 4
    assert {f.detail.split("(")[0] for f in found} == {
        "HTTPConnection", "urllib.request.urlopen", "requests.post",
        "socket.create_connection",
    }


def test_jl024_negative_bounded_calls():
    # the sanctioned shapes: timeout= keyword anywhere, or the
    # positional timeout slot filled (HTTPConnection's third arg,
    # urlopen's third, create_connection's second)
    assert "JL024" not in _codes("""
        import socket
        import urllib.request
        from http.client import HTTPConnection
        import requests

        def register(host, port, url, budget_s):
            conn = HTTPConnection(host, port, timeout=budget_s)
            pos = HTTPConnection(host, port, budget_s)
            page = urllib.request.urlopen(url, None, budget_s)
            resp = requests.post(url, json={}, timeout=budget_s)
            raw = socket.create_connection((host, port), budget_s)
            return conn, pos, page, resp, raw
    """, path=_SERVING_PATH)


def test_jl024_negative_scope_and_lookalikes():
    # non-serving code may rely on defaults (offline tooling), and a
    # LOCAL helper that happens to be named create_connection is not
    # the socket primitive
    src = """
        from http.client import HTTPConnection

        def fetch(host, port):
            return HTTPConnection(host, port)
    """
    assert "JL024" not in _codes(
        src, path="speakingstyle_tpu/training/fake.py"
    )
    assert "JL024" not in _codes("""
        def probe(pool, addr):
            return pool.create_connection(addr)
    """, path=_SERVING_PATH)


def test_jl024_tree_baseline_is_zero():
    """The control plane's bounded-wire claim, structurally: every
    dispatch, heartbeat, registration, and adoption probe in serving/
    passes an explicit timeout (lease/breaker/hedge budgets assume wire
    attempts fail in bounded time)."""
    findings = [f for f in linter.lint_paths() if f.rule == "JL024"]
    assert findings == [], (
        "JL024 must stay at zero tree findings — pass timeout= at every "
        f"serving wire call: {[f.fingerprint for f in findings]}"
    )


# ---------------------------------------------------------------------------
# JL025 — weight-tree precision casts outside the sanctioned helper
# ---------------------------------------------------------------------------


def test_jl025_positive_each_cast_shape():
    src = """
        import jax
        import jax.numpy as jnp

        def shrink(variables, state, teacher_variables):
            a = variables.astype(jnp.bfloat16)
            b = jnp.float32(state.params)
            c = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.bfloat16), teacher_variables)
            return a, b, c
    """
    found = [
        f for f in linter.lint_source(textwrap.dedent(src), _SERVING_PATH)
        if f.rule == "JL025"
    ]
    assert len(found) == 3
    assert all("weight-tree cast" in f.detail for f in found)


def test_jl025_negative_registry_is_sanctioned():
    # the ONE place weight casts are allowed: the cast_params /
    # dequant_params choke point itself
    src = """
        import jax.numpy as jnp

        def cast_params(variables, precision):
            return variables.astype(jnp.bfloat16)
    """
    assert "JL025" not in _codes(
        src, path="speakingstyle_tpu/parallel/registry.py"
    )


def test_jl025_negative_activation_and_nonweight_casts():
    # activations, mels, and non-weight trees cast freely — the rule
    # keys on params/variables naming, not on astype itself
    assert "JL025" not in _codes("""
        import jax
        import jax.numpy as jnp

        def fwd(x, mel, batch):
            y = x.astype(jnp.bfloat16)
            w = mel.astype(jnp.float32)
            z = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), batch)
            return y, w, z
    """, path=_SERVING_PATH)


def test_jl025_tree_baseline_is_zero():
    """The precision-governance claim, structurally: every weight-tree
    cast in the package flows through cast_params in
    parallel/registry.py, so the registry cache key / ProgramCards /
    tier gates see every precision that serves."""
    findings = [f for f in linter.lint_paths() if f.rule == "JL025"]
    assert findings == [], (
        "JL025 must stay at zero tree findings — route weight-tree casts "
        f"through cast_params: {[f.fingerprint for f in findings]}"
    )


# ---------------------------------------------------------------------------
# JL026 — label-cardinality bombs at metric registration sites
# ---------------------------------------------------------------------------


def test_jl026_positive_each_bomb_shape():
    # per-request identity in a label value (direct, attribute,
    # f-string, subscript) and in a dynamic metric name
    src = """
        def handle(self, registry, req_id, payload, r):
            registry.counter("serve_requests_total",
                             labels={"req": req_id}).inc()
            registry.gauge("serve_inflight",
                           labels={"trace": r.trace_id}).set(1)
            registry.histogram("serve_latency_seconds",
                               labels={"who": f"{payload['text']}"})
            registry.counter(f"serve_{req_id}_total").inc()
    """
    found = [
        f for f in linter.lint_source(textwrap.dedent(src), _SERVING_PATH)
        if f.rule == "JL026"
    ]
    assert len(found) == 4
    details = " | ".join(f.detail for f in found)
    assert "req_id" in details and "trace_id" in details
    assert "the metric name" in details


def test_jl026_negative_bounded_labels_and_other_receivers():
    # bounded dynamic labels (class/replica/reason/bucket) are the
    # sanctioned idiom; non-registry receivers and non-serving paths
    # are out of scope
    assert "JL026" not in _codes("""
        def dispatch(self, registry, klass, rid, reason):
            registry.counter("serve_class_requests_total",
                             labels={"class": klass}).inc()
            registry.gauge("serve_replica_busy",
                           labels={"replica": rid}).set(1)
            registry.counter("serve_autoscale_decisions_total",
                             labels={"reason": reason}).inc()
    """, path=_SERVING_PATH)
    assert "JL026" not in _codes("""
        def tally(self, counters, req_id):
            counters.counter("x", labels={"req": req_id})
    """, path=_SERVING_PATH)
    assert "JL026" not in _codes("""
        def tally(self, registry, req_id):
            registry.counter("x", labels={"req": req_id})
    """, path="speakingstyle_tpu/training/fake.py")


def test_jl026_tree_baseline_is_zero():
    """The bounded-cardinality claim, structurally: every metric label
    in serving/ and obs/ is a bounded vocabulary — per-request identity
    rides spans and events, so /metrics stays O(config), not
    O(traffic)."""
    findings = [f for f in linter.lint_paths() if f.rule == "JL026"]
    assert findings == [], (
        "JL026 must stay at zero tree findings — per-request identity "
        f"goes on spans/events, not labels: "
        f"{[f.fingerprint for f in findings]}"
    )


# ---------------------------------------------------------------------------
# JL027 — audio bytes leaving serving code without the quality choke point
# ---------------------------------------------------------------------------


def test_jl027_positive_each_emission_shape():
    # the three emission spellings: float->int16 PCM conversion, RIFF
    # container build, audio-named buffer serialization — each in a
    # function with no validator evidence
    src = """
        import numpy as np

        def collect(self, wav_f):
            wav = wav_f.astype(np.int16)
            return wav

        def container(wav):
            return wav_bytes(wav, 22050)

        def push(self, chunk):
            self.sock.send(chunk.tobytes())
    """
    found = [
        f for f in linter.lint_source(textwrap.dedent(src), _SERVING_PATH)
        if f.rule == "JL027"
    ]
    assert len(found) == 3
    details = " | ".join(f.detail for f in found)
    assert ".astype(int16)" in details
    assert "wav_bytes(...)" in details
    assert "chunk.tobytes()" in details


def test_jl027_negative_validated_paths_and_scope():
    # a quality-gate call in the same function sanctions its emissions
    assert "JL027" not in _codes("""
        import numpy as np

        def collect(self, wav_f, klass):
            wav = wav_f.astype(np.int16)
            self.quality.check(wav, klass=klass, source="stream")
            return wav
    """, path=_SERVING_PATH)
    # validator evidence in an ENCLOSING function sanctions a helper
    # closure's emission (the handler validated what the closure ships)
    assert "JL027" not in _codes("""
        import numpy as np

        def handler(self, wav_f):
            def ship(w):
                return w.astype(np.int16)
            validate_wav(wav_f, 22050, self.qcfg)
            return ship(wav_f)
    """, path=_SERVING_PATH)
    # a generic buffer serialization is not audio; non-serving paths
    # are out of scope
    assert "JL027" not in _codes("""
        def pack(a):
            return a.tobytes()
    """, path=_SERVING_PATH)
    assert "JL027" not in _codes("""
        import numpy as np

        def collect(wav_f):
            return wav_f.astype(np.int16)
    """, path="speakingstyle_tpu/training/fake.py")


def test_jl027_tree_baseline_is_zero():
    """The every-wav-crosses-the-gate claim, structurally: each audio
    emission site in serving/ sits in a function that also passes the
    buffer through obs/quality.py — so the validators, the quality SLO
    stream, and the golden-probe drill see every path."""
    findings = [f for f in linter.lint_paths() if f.rule == "JL027"]
    assert findings == [], (
        "JL027 must stay at zero tree findings — every audio emission "
        f"goes through the quality choke point: "
        f"{[f.fingerprint for f in findings]}"
    )


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

_SUPPRESSIBLE = """
    import jax

    @jax.jit
    def f(x):
        if x > 0:{comment}
            return x
        return -x
"""


def test_inline_disable_specific_rule():
    src = _SUPPRESSIBLE.format(comment="  # jaxlint: disable=JL001")
    assert "JL001" not in _codes(src)


def test_inline_disable_bare():
    src = _SUPPRESSIBLE.format(comment="  # jaxlint: disable")
    assert "JL001" not in _codes(src)


def test_inline_disable_other_rule_does_not_apply():
    src = _SUPPRESSIBLE.format(comment="  # jaxlint: disable=JL004")
    assert "JL001" in _codes(src)


def test_skip_file_directive():
    src = "# jaxlint: skip-file\n" + textwrap.dedent(
        _SUPPRESSIBLE.format(comment="")
    )
    assert linter.lint_source(src, "speakingstyle_tpu/fake.py") == []


def test_directive_in_string_literal_is_ignored():
    src = 's = "# jaxlint: skip-file"\n' + textwrap.dedent(
        _SUPPRESSIBLE.format(comment="")
    )
    assert "JL001" in {
        f.rule for f in linter.lint_source(src, "speakingstyle_tpu/fake.py")
    }


# ---------------------------------------------------------------------------
# baseline mechanics + the real gate
# ---------------------------------------------------------------------------


def test_baseline_compare_is_bidirectional():
    findings = linter.lint_source(textwrap.dedent("""
        import jax

        @jax.jit
        def f(x):
            if x > 0:
                return x
            return -x
    """), "speakingstyle_tpu/fake.py")
    assert findings
    empty = linter.findings_counter([])
    new, stale = linter.compare_to_baseline(findings, empty)
    assert new and not stale
    new, stale = linter.compare_to_baseline(
        [], linter.findings_counter(findings)
    )
    assert stale and not new


def test_baseline_roundtrip(tmp_path):
    findings = linter.lint_source(
        "import jax\n\n@jax.jit\ndef f(x):\n    if x > 0:\n        return x"
        "\n    return -x\n",
        "speakingstyle_tpu/fake.py",
    )
    path = str(tmp_path / "baseline.json")
    linter.save_baseline(findings, path)
    loaded = linter.load_baseline(path)
    new, stale = linter.compare_to_baseline(findings, loaded)
    assert not new and not stale


def test_repo_is_clean_modulo_committed_baseline():
    """THE tier-1 gate: the tree must match analysis/baseline.json exactly.

    New findings => fix them or (if deliberate) run
    `python scripts/lint_jax.py --update-baseline` and commit the diff.
    Stale entries => the hazard was fixed; update the baseline so it
    cannot mask a future regression at the same fingerprint.
    """
    findings = linter.lint_paths()
    baseline = linter.load_baseline()
    assert baseline, "committed baseline is missing or empty"
    new, stale = linter.compare_to_baseline(findings, baseline)
    assert not new, (
        "new jaxlint findings over the committed baseline "
        f"(run scripts/lint_jax.py to see them): {sorted(new)}"
    )
    assert not stale, (
        "stale baseline entries (fixed in code, still listed — run "
        f"scripts/lint_jax.py --update-baseline): {sorted(stale)}"
    )


def test_every_rule_is_non_vacuous():
    """Each JL rule has at least one true finding in the tree (possibly
    baselined) — rules that never fire are dead weight."""
    fired = {f.rule for f in linter.lint_paths()}
    fired |= {fp.split(":", 1)[0] for fp in linter.load_baseline()}
    # JL009–JL012 are deliberately absent: the tree already follows the
    # monotonic-clock duration discipline, syncs (reads a device value
    # back) inside every jit-timing region, bounds every serving queue,
    # AND bounds every serving cache (the StyleService LRU replaced the
    # frontend's unbounded per-path mel dict), so there is nothing to
    # baseline — the desired steady state for preventive rules; their
    # fixtures above keep them non-vacuous. JL013 fires on the real tree
    # via its one baselined hit (the batcher's condition-protected
    # collect wait), so it is covered by the baseline union below.
    # JL014 is likewise deliberately absent: training/ and data/ already
    # device_put against NamedShardings only (the hard pins that remain
    # live in ops/ and obs/, outside the rule's scope on purpose).
    # JL015 is absent because the PR that added it also moved every
    # dispatch-loop staging allocation onto the BufferPool — the rule
    # exists to keep it that way. JL016 is absent because every serving
    # loop already parks stop-aware (the fleet supervisor on its
    # Condition, the autoscaler on its Event) — the remaining sleeps
    # are one-shot (close settle, injected-fault stall), outside loops.
    # JL017 is absent because the one in-scope artifact writer (the
    # checkpoint manifest in training/checkpoint.py) already publishes
    # via temp + fsync + os.replace — the idiom the rule enforces.
    # JL018 is absent BY CONSTRUCTION: the registry migration removed
    # every jax.jit / .lower().compile() spelling from the enforced
    # tree, and test_jl018_tree_baseline_is_zero pins it at zero.
    # JL019 is likewise absent by construction: the long-form subsystem
    # was written streaming-first (Stitcher seams, window yields), and
    # test_jl019_tree_baseline_is_zero pins the accumulate-then-concat
    # count at zero.
    # JL024 is absent by construction too: the cluster tier that made
    # serving/ a wire client shipped with an explicit timeout on every
    # HTTP/socket call (derived from deadline budgets or
    # connect_timeout_s), and test_jl024_tree_baseline_is_zero pins the
    # unbounded-wire count at zero.
    # JL025 is absent by construction as well: the precision lattice
    # shipped with cast_params/dequant_params as the only weight-cast
    # spellings in the tree (the rule exists to keep every future cast
    # inside that choke point), and test_jl025_tree_baseline_is_zero
    # pins the out-of-band count at zero.
    # JL008 is absent since the three micro-benchmark scripts that
    # compiled inside their sweep loops were deleted: nothing in the
    # tree builds a program per loop iteration any more.
    for code in ("JL001", "JL002", "JL003", "JL004", "JL005", "JL006",
                 "JL007"):
        assert code in fired, f"{code} never fires on the real tree"


def test_cli_check_exits_zero_on_repo():
    assert cli.main(["--check"]) == 0


@pytest.mark.parametrize("code,src", [
    ("JL001", "import jax\n\n@jax.jit\ndef f(x):\n    if x > 0:\n"
              "        return x\n    return -x\n"),
    ("JL002", "import numpy as np\nimport jax.numpy as jnp\n\ndef f():\n"
              "    y = jnp.ones((3,))\n    return np.sum(y)\n"),
    ("JL003", "import jax\n\ndef step(state, b):\n"
              "    new_state = state.replace(step=state.step + 1)\n"
              "    return new_state\n\nstep = jax.jit(step)\n"),
    ("JL004", "def loop(bs):\n    t = 0.0\n    for b in bs:\n"
              "        t += b.loss.item()\n    return t\n"),
    ("JL005", "import jax\n\n@jax.jit\ndef f(x, cfg):\n"
              "    return x * cfg.scale\n"),
    ("JL006", "import jax\n\ndef f(rng):\n"
              "    a = jax.random.normal(rng, (2,))\n"
              "    b = jax.random.normal(rng, (2,))\n    return a + b\n"),
    ("JL007", "def f(p):\n    try:\n        return open(p).read()\n"
              "    except Exception:\n        pass\n"),
    ("JL008", "import jax\n\ndef sweep(vs, x):\n    for v in vs:\n"
              "        jax.jit(lambda y: y * v)(x)\n"),
    ("JL010", "import time\nimport jax\n\ndef bench(f, x):\n"
              "    g = jax.jit(f)\n    t0 = time.monotonic()\n"
              "    y = g(x)\n    return time.monotonic() - t0\n"),
    ("JL011", "import queue\n\nq = queue.Queue()\n"),
    ("JL012", "class F:\n    def __init__(self):\n"
              "        self._mel_cache = {}\n"),
    ("JL013", "def serve(future):\n    return future.result()\n"),
    ("JL014", "import jax\n\ndef put(v):\n"
              "    return jax.device_put(v, jax.devices()[0])\n"),
    ("JL015", "import numpy as np\n\ndef handle(reqs):\n    for r in reqs:\n"
              "        buf = np.zeros((8,), np.float32)\n"),
    ("JL016", "import time\n\ndef _supervise(self):\n    while True:\n"
              "        time.sleep(0.25)\n"),
    ("JL017", "def save(ckpt_path, blob):\n"
              "    with open(ckpt_path, \"w\") as fh:\n"
              "        fh.write(blob)\n"),
    ("JL018", "import jax\n\ndef build(fn):\n    return jax.jit(fn)\n"),
    ("JL019", "import numpy as np\n\ndef collect(chunks):\n    out = []\n"
              "    for c in chunks:\n        out.append(c)\n"
              "    return np.concatenate(out)\n"),
    ("JL024", "from http.client import HTTPConnection\n\ndef ping(host):\n"
              "    return HTTPConnection(host, 80)\n"),
    ("JL025", "import jax.numpy as jnp\n\ndef shrink(variables):\n"
              "    return variables.astype(jnp.bfloat16)\n"),
    ("JL026", "def handle(registry, req_id):\n"
              "    registry.counter(\"serve_requests_total\",\n"
              "                     labels={\"req\": req_id}).inc()\n"),
    ("JL027", "import numpy as np\n\ndef collect(wav_f):\n"
              "    return wav_f.astype(np.int16)\n"),
])
def test_cli_exits_nonzero_on_each_positive_fixture(tmp_path, code, src):
    # JL004 is scoped to training/ paths; JL007 to speakingstyle_tpu/;
    # JL011-JL013, JL015, JL016, JL019 and JL024 to
    # speakingstyle_tpu/serving/; JL017 to both training/ and serving/
    # (training default suffices)
    sub = ("serving" if code in ("JL011", "JL012", "JL013", "JL015", "JL016",
                                 "JL019", "JL024", "JL026", "JL027")
           else "training")
    d = tmp_path / "speakingstyle_tpu" / sub
    d.mkdir(parents=True)
    f = d / "fixture.py"
    f.write_text(src)
    rc = cli.main([str(f), "--no-baseline", "--check", "--select", code])
    assert rc == 1, f"{code} positive fixture did not fail the CLI"


def test_cli_rejects_unknown_rule():
    assert cli.main(["--select", "JL999"]) == 2


def test_cli_list_rules():
    assert cli.main(["--list-rules"]) == 0


# ---------------------------------------------------------------------------
# runtime contracts
# ---------------------------------------------------------------------------


def test_contracts_noop_when_disabled(monkeypatch):
    monkeypatch.setattr(contracts, "ENABLED", False)
    x = np.zeros((2, 3))
    assert contracts.assert_shape(x, (99, 99), "x") is x
    assert contracts.assert_rank(x, 7, "x") is x
    assert contracts.assert_dtype(x, "integer", "x") is x
    assert contracts.assert_tree_finite(
        {"a": np.array([np.nan])}, "t"
    ) is not None


def test_contracts_enabled(monkeypatch):
    monkeypatch.setattr(contracts, "ENABLED", True)
    x = np.zeros((2, 3), np.float32)
    # passing specs return the array through
    assert contracts.assert_shape(x, (2, 3), "x") is x
    assert contracts.assert_shape(x, (None, 3), "x") is x
    assert contracts.assert_rank(x, 2, "x") is x
    assert contracts.assert_dtype(x, "floating", "x") is x
    assert contracts.assert_shape(None, (1,), "optional") is None
    with pytest.raises(contracts.ContractError):
        contracts.assert_shape(x, (2, 4), "x")
    with pytest.raises(contracts.ContractError):
        contracts.assert_rank(x, 3, "x")
    with pytest.raises(contracts.ContractError):
        contracts.assert_dtype(x, "integer", "x")
    with pytest.raises(contracts.ContractError):
        contracts.assert_tree_finite({"a": np.array([1.0, np.nan])}, "t")
    contracts.assert_tree_finite({"a": np.array([1.0, 2.0])}, "t")


def test_contracts_tree_finite_skips_tracers(monkeypatch):
    monkeypatch.setattr(contracts, "ENABLED", True)
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        contracts.assert_tree_finite({"x": x}, "inside-jit")
        return x * 2

    # NaN input must NOT raise inside jit (leaves are tracers there);
    # the check belongs at host boundaries
    out = f(jnp.array([jnp.nan]))
    assert np.isnan(np.asarray(out)).all()


def test_contracts_fire_at_trace_time_in_jit(monkeypatch):
    monkeypatch.setattr(contracts, "ENABLED", True)
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        contracts.assert_rank(x, 2, "x")
        return x

    with pytest.raises(contracts.ContractError):
        f(jnp.zeros((3,)))  # wrong rank fails during tracing


def test_length_regulate_contract_integration(monkeypatch):
    monkeypatch.setattr(contracts, "ENABLED", True)
    import jax.numpy as jnp

    from speakingstyle_tpu.ops.length_regulator import length_regulate

    x = jnp.zeros((2, 5, 8))
    good = jnp.ones((2, 5), jnp.int32)
    frames, lens, mask = length_regulate(x, good, 16)
    assert frames.shape == (2, 16, 8)
    with pytest.raises(contracts.ContractError):
        length_regulate(x, jnp.ones((2, 4), jnp.int32), 16)
    with pytest.raises(contracts.ContractError):
        length_regulate(x[0], good, 16)  # rank-2 features
