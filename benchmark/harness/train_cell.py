"""Driver of training cells (traffic ``driver: train_cell``).

One process. Set-up writes the seeded corpus and the seeded weights (as a
checkpoint the program restores), then calls the program's own training loop,
``training.trainer.run_training``, once. The loop's first cycles warm every
shape up; the window opens on the ``train_step`` event that ends the warm-up
and closes on the last ``train_step`` event inside ``--seconds``. The harness
records around the loop's compiled step (the same object the window drives):
the first two steps' batches, losses and state for the comparison with the
plain reference, and nothing after them but a call count.
"""

import gc
import json
import os
import signal
import threading
import time

import numpy as np

from . import common, flops, peaks, tracered, trafficgen
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
        self.batches, self.losses, self.shapes = [], [], []
        self.first_mu = self.params_after = None

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def __call__(self, state, arrays, rng):
        import jax

        k = self.calls
        if k < CAPTURE_STEPS:
            self.batches.append({n: np.asarray(v) for n, v in arrays.items()})
        if k < 2 * self.open_at:
            self.shapes.append(tuple(arrays["mels"].shape[:2])
                               + (arrays["texts"].shape[1],))
        if k == self.open_at:
            self.on_open()
        new_state, losses = self.inner(state, arrays, rng)
        if k < CAPTURE_STEPS:
            self.losses.append(float(jax.device_get(losses["total_loss"])))
            if k == 0:
                self.first_mu = jax.device_get(find_mu(new_state.opt_state))
            if k == CAPTURE_STEPS - 1:
                self.params_after = jax.device_get(new_state.params)
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


def norm_gaps(prog: dict, ref: dict, skip=()):
    """Worst leaf of |‖prog‖ − ‖ref‖| over max(‖ref‖ of that leaf, ‖ref‖ of
    the median leaf). Returns (gap, leaf)."""
    names = [n for n in sorted(ref) if n not in skip]
    ref_norms = {n: float(np.linalg.norm(np.asarray(ref[n], np.float64)))
                 for n in names}
    median = float(np.median(list(ref_norms.values())))
    worst, leaf = 0.0, None
    for n in names:
        p = float(np.linalg.norm(np.asarray(prog[n], np.float64)))
        gap = abs(p - ref_norms[n]) / max(ref_norms[n], median, 1e-30)
        if not gap <= worst:  # NaN counts as worst
            worst, leaf = gap, n
    return worst, leaf


def load_reference(cfg: dict):
    """The configuration's plain reference: the module its file names."""
    import importlib.util

    path = os.path.join(common.ROOT, cfg["reference"])
    spec = importlib.util.spec_from_file_location(
        "bench_reference_" + cfg["name"], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# gradients closer than a hundredth of the reference's norm differ by nothing
DIFF_FLOOR = 1e-4


def compare_training(rec, ref_out, params0, b1: float, other_grad):
    """The numbers compared, from the recorder's captures and the reference's
    (losses, first clipped gradient, parameters after the last step).
    ``other_grad`` is the reference's first gradient again under other
    dropout masks: what two sound draws differ by."""
    from ..reference import fs2

    ref_losses, ref_grad, ref_params = ref_out
    readings, notes = {}, {}
    for i, (lp, lr) in enumerate(zip(rec.losses, ref_losses)):
        readings[f"loss_gap_step{i + 1}"] = abs(lp - lr) / abs(lr)
    ref_grad = {k: np.asarray(v) for k, v in fs2.flatten(ref_grad).items()}
    prog_grad = {k: np.asarray(v) / (1.0 - b1)
                 for k, v in fs2.flatten(rec.first_mu).items()}
    readings["grad_norm_gap"], notes["grad_leaf"] = norm_gaps(prog_grad, ref_grad)
    # the norm of the difference over all leaves together, against the same
    # between two draws of the reference: which rows went in shows here, where
    # a gap of norms is blind to it (a mean over half the rows has the norms)
    sq = lambda t: sum(float(np.sum(np.square(np.asarray(v, np.float64))))
                       for v in t.values())
    other = {k: np.asarray(v) for k, v in fs2.flatten(other_grad).items()}
    mine = sq({k: prog_grad[k] - ref_grad[k] for k in ref_grad}) / sq(ref_grad)
    draws = sq({k: other[k] - ref_grad[k] for k in ref_grad}) / sq(ref_grad)
    readings["grad_diff"], notes["grad_diff_draws"] = mine ** 0.5, draws ** 0.5
    readings["grad_diff_excess"] = abs(mine - draws) / max(draws, DIFF_FLOOR)
    # leaves whose gradient is nought to rounding in the reference move under
    # Adam by round-off alone: out of the change, by a rule on the gradient
    norms = {k: float(np.linalg.norm(v)) for k, v in ref_grad.items()}
    floor = 1e-3 * float(np.median(list(norms.values())))
    skip = sorted(k for k, v in norms.items() if v < floor)
    p0 = fs2.flatten(params0)
    change = lambda tree: {k: np.asarray(v, np.float64) - p0[k]
                           for k, v in fs2.flatten(tree).items()}
    readings["change_norm_gap"], notes["change_leaf"] = norm_gaps(
        change(rec.params_after), change(ref_params), skip)
    notes["left_out"] = skip
    notes["losses"] = {"program": rec.losses, "reference": list(ref_losses)}
    return readings, notes


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
    fs2 = load_reference(cfg)

    work = common.workdir()
    corpus = os.path.join(work, "corpus")
    deck_spec = {**traffic["deck"], "batch_size": traffic["batch_size"],
                 "pitch_range": cfg["model"]["pitch_range"],
                 "energy_range": cfg["model"]["energy_range"]}
    info = trafficgen.write_corpus(corpus, deck_spec, seed,
                                   cfg["model"]["n_mel_channels"])
    read_ms = corpus_read_ms(corpus, traffic["batch_size"])
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
    hp = fs2.hyper(cfg["model"])
    params0 = fs2.init_params(hp, seed)
    stats0 = fs2.init_batch_stats(hp)
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
    opt = common.optimizer_for_reference(prog["train"])
    block = cfg.get("reference_block_rows", 8)
    t_ref = time.time()
    ticks = []
    ref_out = fs2.train_steps(hp, opt, params0, stats0, rec.batches, seed,
                              block_rows=block,
                              clock=lambda name: ticks.append((name, time.time())))
    log("reference phases (s): " + ", ".join(
        f"{b[0]}={b[1] - a[1]:.1f}" for a, b in zip(ticks, ticks[1:])
        if b[0] != "start"))
    other_grad = fs2.train_steps(hp, opt, params0, stats0, rec.batches[:1],
                                 seed + 1, block_rows=block)[1]
    readings, notes = compare_training(rec, ref_out, params0, opt["betas"][0],
                                       other_grad)
    log(f"reference: {time.time() - t_ref:.1f} s; notes "
        f"{json.dumps({k: notes[k] for k in ('grad_leaf', 'change_leaf', 'grad_diff_draws')})}; "
        f"losses {notes['losses']}; readings {json.dumps(readings)}")
    for name in (control or "").split(",") if control else ():
        # the reference put in the program's place, with masks of its own
        # as the program has
        if name == "half_batch":  # the fault: half the rows left out
            rows = [{k: v[: len(v) // 2] for k, v in b.items()}
                    for b in rec.batches]
            ctl = fs2.train_steps(hp, opt, params0, stats0, rows, seed + 1,
                                  block_rows=block)
        elif name == "other_masks":
            ctl = fs2.train_steps(hp, opt, params0, stats0, rec.batches,
                                  seed + 1, block_rows=block)
        else:
            ctl = fs2.train_steps(hp, opt, params0, stats0, rec.batches, seed,
                                  block_rows=block, quant=quantizer(name))
        fake = type("R", (), {})()
        fake.losses = ctl[0]
        fake.first_mu = jax.tree_util.tree_map(
            lambda g: np.asarray(g) * (1.0 - opt["betas"][0]), ctl[1])
        fake.params_after = ctl[2]
        got, where = compare_training(fake, ref_out, params0, opt["betas"][0],
                                      other_grad)
        limits = common.load_json(f"benchmark/limits/{workload}.json")["limits"]
        mine = {k: v for k, v in limits.items() if k in got}
        notes.setdefault("control", {})[name] = got
        log(f"control {name}: correct {common.judge(got, mine)[0]} "
            f"{json.dumps(got)} leaves "
            f"{json.dumps([where['grad_leaf'], where['change_leaf']])}")
    if limits_only:
        print(json.dumps({"seed": seed, "readings": readings,
                          "control": notes.get("control"),
                          "leaves": [notes["grad_leaf"], notes["change_leaf"]],
                          "losses": notes["losses"]}), flush=True)
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
    limits = common.load_json(f"benchmark/limits/{workload}.json")["limits"]
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
        lengths = [(n, int(d.sum())) for n, d in trafficgen.train_deck(deck_spec)]
        cap = cfg["model"]["max_seq_len"]
        lengths = [(min(s, cap), min(t, cap)) for s, t in lengths]
        tr = tracered.compact(trace_dir)
        busy_s, traced_s = tracered.busy_and_window(tr)
        device["busy_s"], device["window_s"] = busy_s, traced_s
        calm = calm_cycles(inside, cycles, warm + t_steps[0],
                           warm + t_steps[1], log_step)
        ctx = {
            "cell": cell, "device": device, "peaks": peaks.peaks_or_none(device["kind"], toy),
            "events": [e for e, _ in calm], "cycles_s": [c for _, c in calm],
            "window_s": sum(c for _, c in calm),
            "trace": tr, "compiles_open": marks["compiles_open"],
            "compiles_close": marks["compiles_close"],
            "shapes": rec.shapes[warm:2 * warm] or rec.shapes,
            "frames_per_cycle": info["frames_per_cycle"],
            "flops_per_cycle": flops.train_step_flops(cfg["model"], lengths),
            "log_step": log_step, "corpus_read_ms": read_ms,
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


def corpus_read_ms(corpus: str, batch_size: int) -> float:
    """Milliseconds to ``np.load`` one batch's feature files (four a row)
    from the working directory, just written: what the file system charges
    the loader, read in set-up so that a slow one is on the record."""
    with open(os.path.join(corpus, "train.txt")) as f:
        names = [line.split("|")[0] for line in f][:batch_size]
    t0 = time.perf_counter()
    for n in names:
        for kind in ("mel", "pitch", "energy", "duration"):
            np.load(os.path.join(corpus, kind, f"S-{kind}-{n}.npy"))
    ms = 1e3 * (time.perf_counter() - t0)
    log(f"corpus_read_ms {ms:.1f} ({len(names)} rows x 4 files)")
    return ms


def read_per_layer(workload: str, ctx: dict) -> dict:
    """Every per-layer metric that lists this cell (or lists none), each from
    its own reader ``benchmark/metrics/<name>.py``; a reader that finds
    nothing to read returns None and the metric is left out."""
    import importlib.util

    out = {}
    for m in common.manifest()["per_layer"]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        path = os.path.join(common.BENCH, "metrics", m["name"] + ".py")
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        try:
            value = mod.read(ctx)
        except (KeyError, LookupError, ZeroDivisionError, TypeError) as e:
            log(f"metric {m['name']}: nothing to read ({type(e).__name__}: {e})")
            value = None
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def quantizer(name: str):
    """The control's rounding: both operands of every product, to ``name``
    and back."""
    import jax.numpy as jnp

    dtype = jnp.dtype(name)
    if jnp.issubdtype(dtype, jnp.integer):
        def q(x):
            scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
            return jnp.round(x / scale).astype(dtype).astype(jnp.float32) * scale
        return q
    return lambda x: x.astype(dtype).astype(jnp.float32)
