"""Driver of training cells (traffic ``driver: train_cell``).

One process. Set-up writes the seeded corpus and the seeded weights (as a
checkpoint the program restores), then calls the program's own training loop,
``training.trainer.run_training``, once. The loop's first cycles warm every
shape up; the window opens on the ``train_step`` event that ends the warm-up
and closes on the last ``train_step`` event inside ``--seconds``. The harness
records around the loop's compiled step (the same object the window drives):
the first two steps' batches, losses and state for the comparison with the
plain reference, and nothing after them but a call count.

What is one configuration's own comes from the module its file names
(``reference``; PERF.md section 4): the seeded weights, the corpus writer,
the operations of a cycle and the comparison that decides ``correct``. The
working directory, the program's YAML, the loop, its events, the window, the
trace, the readers' ``ctx`` and the result line are here, for every
configuration the program trains.
"""

import gc
import json
import os
import signal
import threading
import time

import numpy as np

from . import common, contract, peaks, tracered
from .common import log

# the reference follows the first two steps (two for three: its compile is
# what a cold run pays most for, one program per batch shape)
CAPTURE_STEPS = 2


def enable_cache():
    """The program's own placement of the persistent cache (the environment's
    directory, else ``<checkout>/.jax_cache``), applied before the harness
    compiles anything of its own."""
    from speakingstyle_tpu.obs.jaxmon import enable_compilation_cache

    return enable_compilation_cache()


class StepRecorder:
    """Stands where ``make_train_step``'s jitted function stands."""

    def __init__(self, inner, open_at, on_open):
        self.inner, self.open_at, self.on_open = inner, open_at, on_open
        self.calls = 0
        self.batches, self.losses = [], []
        self.first_mu = None
        self.params_after = []  # the parameters after each captured step

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def __call__(self, state, arrays, rng):
        import jax

        k = self.calls
        if k < CAPTURE_STEPS:
            self.batches.append({n: np.asarray(v) for n, v in arrays.items()})
        if k == self.open_at:
            self.on_open()
        new_state, losses = self.inner(state, arrays, rng)
        if k < CAPTURE_STEPS:
            self.losses.append(float(jax.device_get(losses["total_loss"])))
            if k == 0:
                self.first_mu = jax.device_get(find_mu(new_state.opt_state))
            self.params_after.append(jax.device_get(new_state.params))
        self.calls += 1
        return new_state, losses


def find_mu(opt_state):
    """Adam's first moment inside the program's optimizer state."""
    stack = [opt_state]
    while stack:
        node = stack.pop()
        if hasattr(node, "mu"):
            return node.mu
        if isinstance(node, (tuple, list)):
            stack.extend(node)
    raise LookupError("no Adam first moment in the optimizer state")


def save_seed_checkpoint(pcfg, params, stats):
    """The seeded weights, written through the program's own checkpoint
    manager as step 0, so that ``run_training(restore_step=0)`` starts from
    exactly what the reference starts from."""
    import jax.numpy as jnp

    from speakingstyle_tpu.training.checkpoint import CheckpointManager
    from speakingstyle_tpu.training.optim import make_optimizer
    from speakingstyle_tpu.training.state import TrainState

    import jax

    variables = {
        "params": jax.tree_util.tree_map(jnp.asarray, params),
        "batch_stats": jax.tree_util.tree_map(jnp.asarray, stats),
    }
    state = TrainState.create(variables, make_optimizer(pcfg.train))
    ckpt = CheckpointManager(pcfg.train.path.ckpt_path)
    try:
        ckpt.save(0, state, block=True)
    finally:
        ckpt.close()


def load_reference(cfg: dict):
    """The configuration's plain reference: the module its file names."""
    return common.load_module(cfg["reference"], "bench_reference_" + cfg["name"])


def run(workload, seed, seconds, trace, toy=False, fault_hook=None,
        limits_only=False, control=None):
    """One run of a training cell. ``fault_hook`` breaks the timed path
    underneath (tests); ``control`` names readings to take beside the
    program's with the reference put in its place, separated by commas: a
    rounding (``float8_e4m3fn``), ``half_batch`` (the fault) or
    ``other_masks`` (the reference again with other dropout masks: what two
    sound draws differ by); ``limits_only`` stops after the captured steps
    and returns the readings."""
    spans = {"start": common.T0}
    cell, entry, cfg, traffic = common.cell_files(workload)
    cfg, traffic = common.sized(cfg, toy), common.sized(traffic, toy)
    device = common.require_chip(cell["chips"], toy)
    spans["chip"] = time.time()
    import jax

    enable_cache()
    ref = load_reference(cfg)

    work = common.workdir()
    corpus = os.path.join(work, "corpus")
    info = ref.write_corpus(corpus, cfg, traffic, seed)
    spans["corpus"] = time.time()
    log_step = traffic["log_step"]
    warm = traffic["warmup_cycles"] * log_step
    step = {"total_step": 10 ** 9, "log_step": log_step, "val_step": 10 ** 9,
            "save_step": 10 ** 9, "synth_step": 10 ** 9}
    cfg = {**cfg, "optimizer_overrides": {"batch_size": traffic["batch_size"]}}
    prog = common.write_program_configs(cfg, work, corpus, "", step=step)
    from speakingstyle_tpu import obs
    from speakingstyle_tpu.configs.config import load_config
    from speakingstyle_tpu.training import trainer

    pcfg = load_config(preprocess=prog["paths"]["preprocess"],
                       model=prog["paths"]["model"], train=prog["paths"]["train"])
    hp = ref.hyper(cfg["model"])
    params0 = ref.init_params(hp, seed)
    stats0 = ref.init_batch_stats(hp)
    save_seed_checkpoint(pcfg, params0, stats0)
    spans["weights"] = time.time()

    registry = obs.MetricsRegistry()
    obs.watch_compiles(registry)
    marks = {}

    def on_open():
        marks["compiles_open"] = obs.jaxmon.compile_totals(registry)
        timer = threading.Timer(
            seconds + traffic.get("close_slack_s", 0.3),
            lambda: os.kill(os.getpid(), signal.SIGTERM))
        timer.daemon = True
        timer.start()
        marks["timer"] = timer

    made = trainer.make_train_step
    holder = {}

    def make(*a, **k):
        inner = made(*a, **k)
        if fault_hook:  # tests only: the timed path broken underneath
            inner = fault_hook(inner)
        holder["rec"] = StepRecorder(inner, warm, on_open)
        return holder["rec"]

    trainer.make_train_step = make
    trace_dir = os.path.join(work, "trace") if trace else None
    t_steps = traffic["trace_steps"]
    try:
        state = trainer.run_training(
            pcfg, mesh=None, restore_step=0,
            max_steps=CAPTURE_STEPS if limits_only else None,
            registry=registry, profile_dir=trace_dir,
            profile_steps=(warm + t_steps[0], warm + t_steps[1]))
    finally:
        trainer.make_train_step = made
        if "timer" in marks:
            marks["timer"].cancel()
    marks["compiles_close"] = obs.jaxmon.compile_totals(registry)
    rec = holder["rec"]
    stats = jax.devices()[0].memory_stats() or {}
    log(f"compile cache: {os.environ.get('JAX_COMPILATION_CACHE_DIR')!r}; at window open "
        f"{marks.get('compiles_open')}; at close {marks['compiles_close']}")
    log(f"memory_stats: {json.dumps({k: int(v) for k, v in stats.items()})}")
    device["memory_peak_bytes"] = common.peak_bytes(stats)
    del state
    gc.collect()

    with open(os.path.join(pcfg.train.path.log_path, "events.jsonl")) as f:
        events = [json.loads(line) for line in f if line.strip()]
    steps = [e for e in events if e.get("event") == "train_step"]
    limits = common.load_json(f"benchmark/limits/{workload}.json")["limits"]
    readings, notes = ref.compare(
        cfg, hp, common.optimizer_for_reference(prog["train"]), params0,
        stats0, rec, seed, controls=control.split(",") if control else (),
        limits=limits)
    if limits_only:
        print(json.dumps({"seed": seed, "readings": readings,
                          "control": notes.get("control"),
                          "worst": notes.get("worst"),
                          "losses": notes.get("losses"),
                          "leaf_norms": notes.get("leaf_norms"),
                          "control_leaf_norms": notes.get("control_leaf_norms")}),
              flush=True)
        return (readings, notes) if toy else 0

    opened = [e for e in steps if e["step"] == warm]
    if not opened:
        raise SystemExit(f"the loop never reached step {warm}: "
                         f"{[e['step'] for e in steps]}")
    t_open = opened[0]["ts"]
    inside = [e for e in steps
              if e["step"] > warm and e["ts"] <= t_open + seconds]
    if not inside:
        raise SystemExit("no step boundary inside the window")
    t_close = inside[-1]["ts"]
    frames = [round(e["mel_frames_per_sec"] / e["steps_per_sec"] * log_step)
              for e in inside]
    window_s = t_close - t_open
    cycle_gap = max(abs(f - info["frames_per_cycle"]) for f in frames) \
        / info["frames_per_cycle"]
    readings["frames_per_cycle_gap"] = cycle_gap
    readings["window_compiles"] = (marks["compiles_close"]["compiles"]
                                   - marks["compiles_open"]["compiles"])
    correct, compared = common.judge(readings, limits)

    cycles = [b["ts"] - a["ts"] for a, b in zip([opened[0]] + inside, inside)]
    stall_ms = 1e3 * (max(cycles) - float(np.median(cycles)))
    log(f"window: {len(inside)} cycles of {log_step} steps in {window_s:.3f} s; "
        f"cycle median {np.median(cycles):.3f} s max {max(cycles):.3f} s; "
        f"train_stall_max_ms {stall_ms:.1f}")
    phases = ["start", "chip", "corpus", "weights"]
    log("setup phases (s): " + ", ".join(
        f"{b}={spans[b] - spans[a]:.1f}" for a, b in zip(phases, phases[1:]))
        + f", restore_compile_warmup={t_open - spans['weights']:.1f}")

    metrics = {
        "setup_s": {"value": t_open - common.T0, "unit": "s"},
        "train_frames_per_s": {"value": sum(frames) / window_s,
                               "unit": "frames/s"},
    }
    breakdown = None
    if trace:
        tr = tracered.compact(trace_dir)
        busy_s, traced_s = tracered.busy_and_window(tr)
        device["busy_s"], device["window_s"] = busy_s, traced_s
        calm = calm_cycles(inside, cycles, warm + t_steps[0],
                           warm + t_steps[1], log_step)
        # what a training driver owes the readers: PERF.md section 3
        ctx = {
            "device": device, "peaks": peaks.peaks_or_none(device["kind"], toy),
            "events": [e for e, _ in calm], "cycles_s": [c for _, c in calm],
            "window_s": sum(c for _, c in calm),
            "trace": tr, "compiles_open": marks["compiles_open"],
            "compiles_close": marks["compiles_close"],
            "flops_per_cycle": ref.cycle_flops(cfg, traffic),
            "log_step": log_step,
        }
        metrics = read_per_layer(workload, ctx)
        breakdown = tracered.breakdown(tr)
    common.emit_result(correct, len(inside) * log_step, 0, metrics, device,
                       compared, breakdown)
    return 0


def calm_cycles(inside, cycles, first, last, log_step):
    """(event, cycle) pairs of the window less the cycles that the profiler's
    own start and stop hold for seconds: it starts before step ``first`` + 1
    and stops after step ``last``, at the latest in the cycle after that
    step's. Everything, where nothing else is left."""
    calm = [(e, c) for e, c in zip(inside, cycles)
            if not first < e["step"] <= last + log_step]
    return calm or list(zip(inside, cycles))


def read_per_layer(workload: str, ctx: dict) -> dict:
    """Every per-layer metric that lists this cell, or lists none and moves
    what the cell reports, each from its own reader
    ``benchmark/metrics/<name>.py``; a reader that finds nothing to read
    returns None and the metric is left out."""
    man = common.manifest()
    units = {m["name"]: m["unit"] for m in man["per_layer"]}
    out = {}
    for name in contract.readers_of(man, workload):
        mod = common.load_module(f"benchmark/metrics/{name}.py",
                                 "bench_metric_" + name.replace(".", "_"))
        try:
            value = mod.read(ctx)
        except (LookupError, ZeroDivisionError, TypeError, ValueError) as e:
            log(f"metric {name}: nothing to read ({type(e).__name__}: {e})")
            value = None
        if value is not None:
            out[name] = {"value": float(value), "unit": units[name]}
    return out
