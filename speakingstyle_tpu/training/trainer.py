"""Training orchestration: sharded jit steps + the step loop.

The reference's loop (reference: train.py:79-173) maps here as:
  nn.DataParallel scatter/gather  ->  batch sharded over the mesh's data
                                      axis; XLA inserts the gradient psum
  backward + clip + custom LR     ->  optax chain (training/optim.py)
  periodic log/val/save           ->  callbacks driven by the step counter

The train step is compiled once per batch-bucket shape (data/dataset.py
bucket grid); state is replicated, donated, and updated in place.
"""

import contextlib
import os
import time
from typing import Dict, Iterator, Optional

import jax
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from speakingstyle_tpu import obs
from speakingstyle_tpu.analysis import contracts
from speakingstyle_tpu.configs.config import Config
from speakingstyle_tpu.models import mellum
from speakingstyle_tpu.models.loss import fastspeech2_loss
from speakingstyle_tpu.obs.trace import new_context
from speakingstyle_tpu.ops import blocked_attention, qk_prepare
from speakingstyle_tpu.parallel.registry import ProgramRegistry, jit_program
from speakingstyle_tpu.training import faults, resilience
from speakingstyle_tpu.training.state import TrainState

# keys in the step's losses dict that are sentinel/bookkeeping, not losses
_INTERNAL_LOSS_KEYS = ("_finite", "_moe", "_choices")


def public_losses(losses: Dict) -> Dict:
    return {k: v for k, v in losses.items() if k not in _INTERNAL_LOSS_KEYS}


def build_train_step_card(train_step, state, arrays, rng,
                          program_registry: Optional[ProgramRegistry] = None):
    """ProgramCard (obs/cost.py) for the jitted train step at the given
    batch geometry: XLA's own FLOP/bytes/memory accounting of the step
    program, and the count of Pallas kernels in it. The AOT compile goes
    through the ProgramRegistry (the tree's one compile entry point);
    called after the step's first jit call it is served from jax's
    in-memory executable cache (first chip run, PR 21: one ~108 s
    train-step compile per run, not two). The card is telemetry: a failed
    card compile returns None (with a warning) rather than failing the
    run."""
    registry = (
        program_registry if program_registry is not None
        else ProgramRegistry(counter_name="train_compiles_total",
                             prefix="train")
    )
    try:
        compiled = registry.compile(
            train_step, (state, arrays, rng), name="train_step"
        )
    except Exception as e:
        print(
            "warning: train-step program card unavailable "
            f"({type(e).__name__}: {e})"
        )
        return None
    return obs.ProgramCard.from_compiled(compiled, name="train_step")


def step_kernel_launches(train_step, state, arrays, rng) -> Dict:
    """Launches of the ``decoder_lm`` family's kernels in the jitted train
    step, from the step's jaxpr (traced already: no compile):
    ``qk_prepare_launches`` by variant (``norm``, ``plain``) and
    ``attn_bwd_launches``, the attention core's backward by path (``fused``,
    ``split``); all 0 where the model's plain ``jax.numpy`` ran, as off a
    TPU. Beside them ``loss_head_products``, the products on the head's
    ``[d, V]`` (``mellum.head_products``: 3 a chunk's loop body). Telemetry:
    each None with a warning where the step cannot be read."""
    try:
        jaxpr = train_step.trace(state, arrays, rng).jaxpr
    except Exception as e:
        print(f"warning: kernel launches unavailable ({type(e).__name__}: {e})")
        return dict.fromkeys(("qk_prepare_launches", "attn_bwd_launches",
                              "loss_head_products"))
    return {"qk_prepare_launches": qk_prepare.launches(jaxpr),
            "attn_bwd_launches": blocked_attention.launches(jaxpr),
            "loss_head_products": mellum.head_products(
                jaxpr, state.params[mellum.HEAD]["kernel"].shape)}


def _model_kwargs(arrays: Dict, teacher_forced: bool) -> Dict:
    kw = dict(
        speakers=arrays["speakers"],
        texts=arrays["texts"],
        src_lens=arrays["src_lens"],
        mels=arrays["mels"],
        mel_lens=arrays["mel_lens"],
        max_mel_len=arrays["mels"].shape[1],
    )
    if teacher_forced:
        kw.update(
            p_targets=arrays["pitches"],
            e_targets=arrays["energies"],
            d_targets=arrays["durations"],
        )
    return kw


def make_train_step(model, tx, cfg: Config, mesh=None, state_shardings=None):
    """Returns jitted fn(state, arrays, rng) -> (state, losses).

    ``state_shardings`` (a TrainState pytree of NamedShardings, see
    parallel/partition.train_state_shardings) engages tensor parallelism
    over the mesh's ``model`` axis; omitted, the state is replicated
    (pure DP — the reference's only strategy, SURVEY.md §2.4).

    With ``train.resilience.nan_sentinel`` the step also returns
    ``losses["_finite"]`` — an on-device all-finite reduction over losses
    and grads, read host-side only at the log boundary (run_training's
    rollback trigger; stripped from logs by ``public_losses``).
    """
    lambda_f = cfg.train.loss.lambda_f
    p_level = cfg.preprocess.preprocessing.pitch.feature
    e_level = cfg.preprocess.preprocessing.energy.feature
    nan_sentinel = cfg.train.resilience.nan_sentinel
    lm = cfg.model.family == "decoder_lm"

    def acoustic_loss(params, state, arrays, rng):
        # trace-time contracts: shape/dtype metadata only, so these run
        # (and fail) during tracing and add nothing to the compiled step
        B = arrays["texts"].shape[0]
        contracts.assert_rank(arrays["texts"], 2, "train_step.texts")
        contracts.assert_rank(arrays["mels"], 3, "train_step.mels")
        contracts.assert_shape(arrays["src_lens"], (B,), "train_step.src_lens")
        contracts.assert_shape(arrays["mel_lens"], (B,), "train_step.mel_lens")
        contracts.assert_shape(
            arrays["durations"], arrays["texts"].shape, "train_step.durations"
        )
        out, updates = model.apply(
            {"params": params, "batch_stats": state.batch_stats},
            **_model_kwargs(arrays, teacher_forced=True),
            deterministic=False,
            rngs={"dropout": rng},
            mutable=["batch_stats"],
        )
        losses = fastspeech2_loss(
            out,
            arrays["mels"],
            arrays["pitches"],
            arrays["energies"],
            arrays["durations"],
            params,
            lambda_f=lambda_f,
            pitch_feature_level=p_level,
            energy_feature_level=e_level,
        )
        return losses["total_loss"], (losses, updates["batch_stats"])

    def lm_loss(params, state, arrays, rng):
        # the family's loss over the held vocabulary rows (next-token
        # cross-entropy, or the masked-diffusion loss on the loader's noise);
        # the routing's counts ride back beside it (``_moe``: read by the host
        # at the log boundary only, as the sentinel is) and so do the
        # router's choices (``_choices``: the loop never reads them)
        contracts.assert_rank(arrays["tokens"], 2, "train_step.tokens")
        loss, aux = model.apply({"params": params}, **mellum.batch_inputs(arrays))
        choices = aux.pop("choices")
        return loss, ({"total_loss": loss, "_moe": aux, "_choices": choices},
                      state.batch_stats)

    loss_of = lm_loss if lm else acoustic_loss

    def step_fn(state: TrainState, arrays: Dict, rng) -> tuple:
        rng = jax.random.fold_in(rng, state.step)
        (_, (losses, batch_stats)), grads = jax.value_and_grad(
            lambda params: loss_of(params, state, arrays, rng), has_aux=True
        )(state.params)
        if nan_sentinel:  # trace-time flag: compiled in or out, never branched
            losses = dict(losses)
            flag = resilience.all_finite(public_losses(losses), grads)
            if mesh is not None:
                # explicit dp-axis reduction: pin the flag fully replicated
                # so GSPMD compiles the all-reduce over the data axis into
                # the step itself — every device holds the same verdict and
                # every host reads the same rollback decision (one shard's
                # NaN trips all of them; drilled by the nan_grads DP fault)
                flag = jax.lax.with_sharding_constraint(
                    flag, NamedSharding(mesh, P())
                )
            losses["_finite"] = flag
        updates, opt_state = tx.update(grads, state.opt_state, state.params)
        params = optax.apply_updates(state.params, updates)
        new_state = state.replace(
            step=state.step + 1,
            params=params,
            batch_stats=batch_stats,
            opt_state=opt_state,
        )
        return new_state, losses

    if mesh is None:
        return jit_program(step_fn, donate_argnums=(0,))
    repl = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P("data"))
    if state_shardings is None:
        state_shardings = repl  # pure DP: state fully replicated
    return jit_program(
        step_fn,
        in_shardings=(state_shardings, data, repl),
        out_shardings=(state_shardings, repl),
        donate_argnums=(0,),
    )


def make_eval_step(model, cfg: Config, mesh=None, state_shardings=None):
    """Teacher-forced loss evaluation (reference: evaluate.py:39-58)."""
    lambda_f = cfg.train.loss.lambda_f
    p_level = cfg.preprocess.preprocessing.pitch.feature
    e_level = cfg.preprocess.preprocessing.energy.feature

    def eval_fn(state: TrainState, arrays: Dict) -> Dict:
        if cfg.model.family == "decoder_lm":
            loss, _ = model.apply({"params": state.params},
                                  **mellum.batch_inputs(arrays))
            return {"total_loss": loss}
        out = model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            **_model_kwargs(arrays, teacher_forced=True),
            deterministic=True,
        )
        return fastspeech2_loss(
            out,
            arrays["mels"],
            arrays["pitches"],
            arrays["energies"],
            arrays["durations"],
            state.params,
            lambda_f=lambda_f,
            pitch_feature_level=p_level,
            energy_feature_level=e_level,
        )

    if mesh is None:
        return jit_program(eval_fn)
    repl = NamedSharding(mesh, P())
    data = NamedSharding(mesh, P("data"))
    if state_shardings is None:
        state_shardings = repl
    return jit_program(
        eval_fn, in_shardings=(state_shardings, data), out_shardings=repl
    )


def make_predict_step(model, cfg: Config, mesh=None):
    """Free-running synthesis step (style mel in, no p/e/d targets)."""

    def predict_fn(
        state: TrainState,
        arrays: Dict,
        max_mel_len: int,
        p_control: float = 1.0,
        e_control: float = 1.0,
        d_control: float = 1.0,
    ):
        return model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            speakers=arrays["speakers"],
            texts=arrays["texts"],
            src_lens=arrays["src_lens"],
            mels=arrays["mels"],
            mel_lens=arrays["mel_lens"],
            max_mel_len=max_mel_len,
            p_control=p_control,
            e_control=e_control,
            d_control=d_control,
            deterministic=True,
        )

    return jit_program(predict_fn, static_argnums=(2,))


def evaluate(eval_step, state, batches: Iterator) -> Dict[str, float]:
    """Batch-size-weighted mean of every loss over a val pass
    (reference: evaluate.py:39-58)."""
    sums: Dict[str, float] = {}
    count = 0
    for batch, arrays in batches:
        losses = eval_step(state, arrays)
        n = batch.n_real
        count += n
        for k, v in losses.items():
            sums[k] = sums.get(k, 0.0) + float(v) * n
    if count == 0:
        return {}
    return {k: v / count for k, v in sums.items()}


# What a ``train_step`` event says of its window beyond the losses: each
# field is the growth, between two log boundaries, of the histogram sum
# (spans, ``<name>_seconds``) or the counter beside it, over the window's
# steps. Spans of the step loop first, then the prefetch worker's.
_WINDOW_HISTOGRAMS = {
    "data_wait_s": "train_data_wait_seconds",
    "dispatch_s": "train_step_seconds",
    "sync_s": "train_sync_seconds",
    "log_s": "train_log_seconds",
    "loader_fetch_s": "loader_fetch_seconds",
    "loader_collate_s": "loader_collate_seconds",
    "loader_h2d_s": "loader_h2d_seconds",
    "loader_blocked_s": "loader_blocked_seconds",
}
_WINDOW_COUNTERS = {
    "loader_read_s": "loader_read_seconds_total",
    "loader_cache_hits": "loader_cache_hits_total",
    "loader_cache_misses": "loader_cache_misses_total",
    "frames_real": "train_frames_real_total",
    "frames_padded": "train_frames_padded_total",
}


# the decoder_lm family's routing counters, per step of the window as the
# others are (``_count_routing`` feeds them at the log boundary)
_MOE_WINDOW_COUNTERS = {
    "moe_pairs_held": "moe_pairs_held_total",
    "moe_pairs_dropped": "moe_pairs_dropped_total",
    "moe_expert_tokens_max": "moe_expert_tokens_max_total",
    "moe_expert_tokens_mean": "moe_expert_tokens_mean_total",
    "moe_tiles_used": "moe_tiles_used_total",
}


# the block-diffusion objective's, fed beside them: the positions the
# loader masked and the sum of their loss weights
_DIFFUSION_WINDOW_COUNTERS = {
    "tokens_masked": "train_tokens_masked_total",
    "loss_weight": "train_loss_weight_total",
}


def _window_totals(registry, moe: bool = False,
                   diffusion: bool = False) -> Dict[str, float]:
    """The sums behind a ``train_step`` event's window fields, as they
    stand now; a window's share is the difference of two readings."""
    out = {field: registry.histogram(name).sum
           for field, name in _WINDOW_HISTOGRAMS.items()}
    counters = {**_WINDOW_COUNTERS, **(_MOE_WINDOW_COUNTERS if moe else {}),
                **(_DIFFUSION_WINDOW_COUNTERS if diffusion else {})}
    out.update((field, registry.value(name)) for field, name in counters.items())
    return out


def _count_routing(registry, pending: list, parent) -> None:
    """The routing counts the steps since the last log boundary returned
    (``losses["_moe"]``, already computed: the boundary has synced) into the
    registry: pairs the held experts computed, pairs routed to a held expert
    that got no row (0: nothing is ever dropped), and per step the sum over
    layers of the fullest held expert's pairs and of the mean, and the row
    tiles the dispatch plans used (what every pass over the sorted rows
    costs). The last step's per-layer numbers also go into the span ring
    (``moe_load``), with the worst case the buffers are sized for."""
    if not pending:
        return
    held = registry.counter(
        "moe_pairs_held_total",
        help="(token, choice) pairs computed by the experts held here")
    dropped = registry.counter(
        "moe_pairs_dropped_total",
        help="pairs routed to a held expert that got no row (always 0)")
    most = registry.counter(
        "moe_expert_tokens_max_total",
        help="per step, summed over layers: pairs of the fullest held expert")
    mean = registry.counter(
        "moe_expert_tokens_mean_total",
        help="per step, summed over layers: mean pairs of a held expert")
    tiles = registry.counter(
        "moe_tiles_used_total",
        help="per step, summed over layers and rows: row tiles the plans used")
    fetched = jax.device_get(pending)
    counts = np.stack([aux["expert_counts"] for aux in fetched])   # [steps, layers, held]
    placed = np.stack([aux["pairs_placed"] for aux in fetched])    # [steps, layers]
    lost = np.stack([aux["pairs_routed"] for aux in fetched]) - placed
    held.inc(placed.sum().item())
    dropped.inc(lost.sum().item())
    most.inc(counts.max(axis=2).sum().item())
    mean.inc(counts.mean(axis=2).sum().item())
    tiles.inc(sum(aux["tiles_used"].sum().item() for aux in fetched))
    if "tokens_masked" in fetched[0]:  # the block-diffusion objective's two
        registry.counter(
            "train_tokens_masked_total",
            help="positions of the noised stream the loader masked",
        ).inc(sum(aux["tokens_masked"].item() for aux in fetched))
        registry.counter(
            "train_loss_weight_total",
            help="the sum of the masked positions' loss weights (1 / t)",
        ).inc(sum(aux["loss_weight"].item() for aux in fetched))
    obs.Span.record(
        "moe_load", time.time(), 0.0, parent=parent,
        tokens_max=counts[-1].max(axis=1).tolist(),
        tokens_mean=counts[-1].mean(axis=1).tolist(),
        pairs_held=placed[-1].tolist(), pairs_dropped=lost[-1].tolist(),
        moe_tiles_used=fetched[-1]["tiles_used"].sum().item(),
        moe_tiles_worst=fetched[-1]["tiles_worst"].sum().item())
    pending.clear()


# run_training's mesh default: "resolve from cfg.train.parallel". An
# explicit mesh=None pins the single-chip path even when the config block
# names a mesh (the CLI's flag-override contract).
_MESH_FROM_CONFIG = object()


def run_training(
    cfg: Config,
    mesh=_MESH_FROM_CONFIG,
    restore_step: Optional[int] = None,
    max_steps: Optional[int] = None,
    synth_callback=None,
    log: bool = True,
    vocoder=None,
    profile_dir: Optional[str] = None,
    profile_steps: tuple = (10, 20),
    registry: Optional[obs.MetricsRegistry] = None,
):
    """The full training loop (reference: train.py:21-173).

    Returns the final TrainState. `max_steps` overrides total_step (tests);
    `synth_callback(state, batch, arrays, step, model)` runs every
    synth_step — pass "default" for the GT-vs-predicted sample renderer.
    `profile_dir` enables a jax.profiler trace over the step window
    ``profile_steps`` (greenfield vs the reference — SURVEY.md §5).

    Fault tolerance (``cfg.train.resilience``, ARCHITECTURE.md
    "Resilience"): checkpoint saves are async and a final checkpoint is
    always flushed — at loop end and on SIGTERM/SIGINT (preemption);
    non-finite losses/grads at a log boundary roll the run back to the
    last good checkpoint with a diverged data stream, aborting with
    ``TrainingDivergedError`` after ``max_rollbacks`` consecutive trips;
    loader errors are retried then quarantined per sample. Faults from
    ``SPEAKINGSTYLE_FAULTS`` (training/faults.py) are injected to drill
    each of those paths.

    Telemetry (``speakingstyle_tpu/obs``, ARCHITECTURE.md
    "Observability"): the main thread's time is split over four spans
    (obs/trace.py) that feed ``registry`` histograms and, while a
    profile is taken, the profiler's host plane: ``train_data_wait``
    (blocked on the prefetcher), ``train_dispatch`` (the jitted call's
    enqueue, inside a ``jax.profiler.StepTraceAnnotation``; histogram
    ``train_step_seconds``), and at a log boundary ``train_sync`` (the
    device catching up) and ``train_log`` (what logging costs the
    host). The prefetch worker's spans are in data/prefetch.py and
    data/dataset.py. Via TrainLogger the loop appends structured JSONL
    events (``train_step``/``val``/``checkpoint_save``/``rollback``/
    ``fault_fire``/``preempt_flush``/``quarantine``; schema in
    obs/events.py) to a rotating ``events.jsonl`` under
    ``train.path.log_path`` (``train.obs.*`` knobs); a ``train_step``
    event carries its window's share of every span above and how many
    of its samples the loader served from host memory and from files
    (``loader_cache_hits`` / ``loader_cache_misses``). Set-up is
    four spans under one trace id in the process's span ring
    (``setup_model_init``, ``setup_restore``, ``setup_build_steps``,
    ``setup_datasets``) and ``run_start`` (from there to the loop's
    first data wait), joined there by the run's first ``train_data_wait``,
    each batch shape's first ``train_dispatch``, the program card's build,
    and one ``jax_compile`` record for every compile of the run, under the
    span it happened in (obs/jaxmon.py: program, cache outcome, its trace
    and lowering seconds, the loop's step; a compile past the loop's first
    step is also a ``compile`` event). A ``train_start`` event records
    the build identity (git SHA, jax versions, backend, device count),
    the set-up spans' durations
    and the bytes of host memory the datasets may keep samples in
    (``loader_cache_budget_bytes``, data/dataset.CacheBudget); after
    the first step compiles, a one-time ``program_card`` event records XLA's own cost/memory accounting of
    the step program (obs/cost.py; gated by ``train.obs.program_card``),
    which also backs the ``device_memory_watermark_bytes`` gauge at log
    boundaries.
    """
    t_entry = time.monotonic()
    import jax.numpy as jnp

    from speakingstyle_tpu.data import (
        BucketedBatcher,
        CacheBudget,
        DevicePrefetcher,
        PackedBatcher,
        SpeechDataset,
        TokenDataset,
    )
    from speakingstyle_tpu.models.factory import build_model, init_variables
    from speakingstyle_tpu.training.checkpoint import CheckpointManager
    from speakingstyle_tpu.training.optim import make_lr_schedule, make_optimizer

    from speakingstyle_tpu.parallel.mesh import local_batch_size, resolve_mesh

    steps = cfg.train.step
    res = cfg.train.resilience
    total_step = max_steps if max_steps is not None else steps.total_step
    plan = faults.FaultPlan.from_env()

    # train.parallel.* is the multichip contract: mesh=[1,1] resolves to
    # None and this function behaves exactly as the single-chip path; an
    # explicitly passed mesh — including an explicit None — wins (tests,
    # cli flag overrides)
    if mesh is _MESH_FROM_CONFIG:
        mesh = resolve_mesh(cfg.train.parallel)
    if mesh is not None:
        # startup divisibility gate: fails with the two nearest valid
        # batch sizes named, before any compile or transfer
        local_batch_size(cfg.train.optimizer.batch_size, mesh)

    registry = registry if registry is not None else obs.get_registry()
    # the run's trace: set-up phases, first calls and the card build go
    # into the span ring under it; per-step spans carry no trace id and
    # stay out (the ring holds thousands of records, a run millions of steps)
    run_ctx = new_context(f"train-{os.getpid():x}-{time.time():.0f}")
    # every compile of the run is a ``jax_compile`` ring record under the
    # span open on its thread, else under the run (obs/jaxmon.py); in the
    # loop it carries the step, and past the first step it is an event too
    compiles = obs.jaxmon.watch_run(run_ctx)
    setup_spans = []

    def setup_span(name: str) -> obs.Span:
        setup_spans.append(obs.Span(name, registry=registry, parent=run_ctx))
        return setup_spans[-1]

    # one compile entry point for the run: places the persistent compile
    # cache BEFORE the first jit-on-call compile and counts/publishes
    # per-program cards for anything compiled through it (the train-step
    # ProgramCard below)
    program_registry = ProgramRegistry(
        registry,
        cache_dir=cfg.train.obs.compilation_cache_dir or None,
        counter_name="train_compiles_total",
        prefix="train",
    )
    # registered here for their help text; the loop's spans observe into them
    registry.histogram(
        "train_step_seconds",
        help="per-step host dispatch of the jitted step alone (enqueue "
             "time; the device catching up is train_sync_seconds)",
    )
    registry.histogram(
        "train_data_wait_seconds",
        help="per-step time blocked on the prefetcher",
    )
    steps_ctr = registry.counter("train_steps_total", help="optimizer steps run")
    rollback_ctr = registry.counter(
        "train_rollbacks_total", help="NaN-sentinel rollbacks taken"
    )
    save_ctr = registry.counter(
        "checkpoint_saves_total", help="checkpoints enqueued/flushed"
    )
    fault_ctr = registry.counter(
        "faults_fired_total", help="injected faults fired (drills)"
    )
    # a frame is one position of the sequence the decoder runs over: a mel
    # frame of the acoustic family, a token of the decoder_lm family
    frames_real_ctr = registry.counter(
        "train_frames_real_total",
        help="real mel frames (decoder_lm: positions) handed to the step"
    )
    frames_padded_ctr = registry.counter(
        "train_frames_padded_total",
        help="mel frames (decoder_lm: positions) of the padded batch shapes "
             "handed to the step",
    )
    mem_gauge = registry.gauge(
        "device_memory_watermark_bytes",
        help="device memory watermark: backend memory_stats peak where "
             "available, else ProgramCard argument+temp bytes",
    )

    if cfg.train.fast_prng:
        jax.config.update("jax_default_prng_impl", "rbg")

    with setup_span("setup_model_init"):
        model = build_model(cfg)
        rng = jax.random.PRNGKey(cfg.train.seed)
        tx = make_optimizer(cfg.train)
        # no name is kept on the initial variables: once a restore replaces
        # the state they are gigabytes of the decoder_lm family's device memory
        state = TrainState.create(init_variables(model, cfg, rng), tx)
        schedule = make_lr_schedule(cfg.train)

    # the checkpoint manager, the state's placement on the mesh, and the
    # restore where one is asked for
    with setup_span("setup_restore"):
        ckpt = CheckpointManager(
            cfg.train.path.ckpt_path,
            max_to_keep=res.max_to_keep or None,
            async_save=res.async_checkpointing,
            keep_best=res.keep_best,
            fault_plan=plan,
            registry=registry,
        )

        state_shardings = None
        tp_rules = None
        if mesh is not None:
            from speakingstyle_tpu.parallel.partition import (
                parse_rule_overrides,
                shard_train_state,
                train_state_shardings,
            )

            if cfg.train.parallel.partition_rules:
                tp_rules = parse_rule_overrides(
                    cfg.train.parallel.partition_rules)
            if mesh.shape.get("model", 1) > 1:
                state_shardings = train_state_shardings(state, mesh, tp_rules)
                state = shard_train_state(state, mesh, tp_rules)
            else:
                state = jax.device_put(state, NamedSharding(mesh, P()))

        if restore_step is not None:
            # cross-mesh-shape resume: the restore runs AFTER sharding, so
            # the state passed in already carries THIS run's (target) mesh
            # layout. CheckpointManager.restore builds its abstract template
            # from those shardings and Orbax materializes the checkpoint
            # directly into the target layout — whatever mesh shape wrote
            # it (save on 8x1, restore onto 4x2 or 1x1).
            state = ckpt.restore(
                state,
                step=restore_step if restore_step > 0 else None,
                ignore_layers=cfg.train.ignore_layers,
            )

    with setup_span("setup_build_steps"):
        train_step = make_train_step(
            model, tx, cfg, mesh=mesh, state_shardings=state_shardings
        )
        eval_step = make_eval_step(
            model, cfg, mesh=mesh, state_shardings=state_shardings
        )

    max_src = max_mel = cfg.model.max_seq_len
    pad_mult = mesh.shape["data"] if mesh is not None else 1
    step = int(state.step)
    start_step = step  # profile window is relative to where this run begins
    # by family: what a sample is (an utterance's features, a document's
    # ids) and how samples become batches (bucket padding, packing); cache,
    # budget, fetch spans, quarantine and prefetcher are the same
    lm = cfg.model.family == "decoder_lm"
    diffusion = lm and cfg.model.decoder_lm.block_diffusion
    dataset_cls = TokenDataset if lm else SpeechDataset

    def make_batcher(ds, seed: int, reg, quarantine=None):
        if lm:
            m = cfg.model.decoder_lm
            return PackedBatcher(
                ds, m.seq_len, m.eod_id, seed=seed, quarantine=quarantine,
                registry=reg, trace_parent=run_ctx,
                noise=(m.block_length, m.mask_id) if m.block_diffusion else None)
        return BucketedBatcher(
            ds, max_src=max_src, max_mel=max_mel,
            batch_pad_multiple=pad_mult, seed=seed, quarantine=quarantine,
            registry=reg)

    def make_stream(retry: int) -> DevicePrefetcher:
        # the data seed folds in the resume point AND the rollback retry
        # counter, so a resumed run doesn't replay the original stream
        # from its beginning and a rolled-back run diverges past the
        # batch window that tripped the sentinel
        batcher = make_batcher(
            train_ds, cfg.train.seed + start_step + 7919 * retry, registry,
            quarantine)
        return DevicePrefetcher(
            iter(batcher), mesh=mesh, transfer_retries=res.loader_retries,
            transfer_backoff=res.loader_backoff, registry=registry,
        )

    def fresh_state() -> TrainState:
        # deterministic re-init (same seed): the rollback target when the
        # sentinel trips before any checkpoint exists
        s = TrainState.create(
            init_variables(model, cfg, jax.random.PRNGKey(cfg.train.seed)), tx
        )
        if mesh is not None:
            if state_shardings is not None:
                from speakingstyle_tpu.parallel.partition import shard_train_state

                s = shard_train_state(s, mesh, tp_rules)
            else:
                s = jax.device_put(s, NamedSharding(mesh, P()))
        return s

    with setup_span("setup_datasets"):
        # one budget of host memory for the samples both datasets keep
        # after their first read; a rollback's new stream reads the same
        # train_ds, so what it holds survives
        sample_cache = CacheBudget()
        train_ds = dataset_cls(
            "train.txt", cfg, sort=True, drop_last=True,
            retries=res.loader_retries, backoff=res.loader_backoff,
            fault_plan=plan, cache=sample_cache,
        )
        quarantine = resilience.Quarantine(budget=res.bad_sample_budget)
        prefetch = make_stream(0)
        val_ds = dataset_cls("val.txt", cfg, sort=False, drop_last=False,
                             cache=sample_cache)
        # the validation stream's loader spans observe into a registry of
        # their own: a train_step event's window fields are deltas of the
        # run's registry and count the training loader alone
        val_registry = obs.MetricsRegistry()
        val_batcher = make_batcher(val_ds, 0, val_registry)

    # what stands between set-up and the loop's first data wait (the logger,
    # the build identity, the step's key, the rollback template) is the
    # run's ``run_start`` ring span; ``train_start.setup_s`` holds the four
    # set-up phases, finished before it is written
    with obs.Span("run_start", registry=registry, parent=run_ctx):
        logger = None
        if log:
            events = (
                obs.JsonlEventLog(
                    cfg.train.path.log_path,
                    max_bytes=cfg.train.obs.events_max_bytes,
                    keep=cfg.train.obs.events_keep,
                )
                if cfg.train.obs.events else None
            )
            logger = TrainLogger(
                cfg.train.path.log_path, registry=registry, events=events
            )
        # per-chip observability: gauge labels name each mesh device; on the
        # single-chip path the one label is the default device
        mesh_devices = (
            list(mesh.devices.flat) if mesh is not None else jax.devices()[:1]
        )
        if logger:
            # one identity record per run: build + runtime stack + mesh shape,
            # so a log directory is attributable without the shell that
            # launched it; and where the start's seconds went, phase by phase
            # (``total``: entry of run_training to here, imports included)
            setup_s = {sp.name.removeprefix("setup_"): sp.duration_s
                       for sp in setup_spans}
            setup_s["total"] = time.monotonic() - t_entry
            logger.event(
                "train_start", step=step, total_step=total_step,
                mesh_shape=(dict(mesh.shape) if mesh is not None
                            else {"data": 1, "model": 1}),
                mesh_devices=len(mesh_devices),
                checkpoint_step=ckpt.last_restored_step,
                weights_digest=ckpt.last_weights_digest,
                setup_s=setup_s,
                loader_cache_budget_bytes=sample_cache.limit,
                **obs.build_info(),
            )
        if lm:
            synth_callback = None  # nothing to render: no mel, no vocoder
        if synth_callback == "default":
            synth_callback = default_synth_callback(cfg, logger, vocoder=vocoder)
        step_rng = jax.random.PRNGKey(cfg.train.seed + 1)
        # the train-step ProgramCard is built once, after the first step has
        # compiled (train.obs.program_card); card_pending makes it one
        # attempt, success or not. The decoder_lm family's span also says how
        # often its kernels (``ops/qk_prepare``'s, the attention core's
        # backward) stand in the step, card or no card
        program_card, card_pending = None, cfg.train.obs.program_card or lm

        # template for rollback restores: stays valid after donation consumes
        # the live buffers (see TrainState.abstract)
        abstract_template = state.abstract()
        guard = resilience.RollbackGuard(res.max_rollbacks)
        last_val: Optional[float] = None
        last_saved: Optional[int] = None
        events_log = logger.events if logger else None
        compiles.events, compiles.first_step = events_log, start_step
        seen_shapes = set()

        @contextlib.contextmanager
        def dispatch_span(shape: tuple):
            """``train_dispatch`` around the jitted call. A batch shape's first
            call also goes into the ring, with the shape; its trace, lowering
            and compile (or load from the persistent cache) are its children
            there, the ``jax_compile`` records."""
            first = shape not in seen_shapes
            seen_shapes.add(shape)
            ring = {"parent": run_ctx, "shape": list(shape)} if first else {}
            with obs.Span("train_dispatch", registry=registry,
                          histogram="train_step_seconds", **ring):
                yield

        # a window runs from one log boundary's train_log span to the next's:
        # the four main-thread spans inside it are disjoint, so their sum can
        # not pass the window's wall time
        window_t0, window_step0 = time.monotonic(), step
        window_totals = _window_totals(registry, lm, diffusion)
        moe_pending = []  # each step's routing counts, read at the log boundary
        trace_active = False
        shutdown = resilience.GracefulShutdown()
    try:
        with shutdown:
            while step < total_step and not shutdown.requested:
                compiles.step = step
                # the run's first wait (before any call: the first
                # super-batch's loads) goes into the ring as well
                first_wait = {} if seen_shapes else {"parent": run_ctx}
                with obs.Span("train_data_wait", registry=registry,
                              **first_wait):
                    item = next(prefetch, None)
                if item is None:
                    break
                batch, arrays = item
                if plan.fire("nan_grads", step + 1):
                    # under a DP mesh the poison is shard-local (one
                    # device's rows only): the harsher drill — the
                    # sentinel's dp-axis reduction must trip everywhere
                    arrays = faults.poison_batch(arrays, mesh=mesh)
                    fault_ctr.inc()
                    if logger:
                        logger.event("fault_fire", kind="nan_grads",
                                     step=step + 1)
                if (
                    profile_dir is not None
                    and not trace_active
                    and profile_steps[0] <= step - start_step < profile_steps[1]
                ):
                    with obs.Span("profile_start", registry=registry,
                                  events=events_log, dir=profile_dir,
                                  step=step):
                        jax.profiler.start_trace(profile_dir)
                    trace_active = True
                # step_fn folds state.step into the key, so passing the same
                # step_rng every iteration yields a fresh per-step stream
                with jax.profiler.StepTraceAnnotation("train", step_num=step), \
                        dispatch_span(batch.shape):
                    state, losses = train_step(state, arrays, step_rng)  # jaxlint: disable=JL006
                if "_moe" in losses:
                    moe_pending.append(losses["_moe"])
                step += 1
                steps_ctr.inc()
                if card_pending:
                    card_pending = False
                    with obs.Span("train_program_card", registry=registry,
                                  parent=run_ctx) as card_span:
                        if lm:
                            card_span.note(**step_kernel_launches(
                                train_step, state, arrays, step_rng))
                        if cfg.train.obs.program_card:
                            program_card = build_train_step_card(
                                train_step, state, arrays, step_rng,
                                program_registry=program_registry,
                            )
                    if program_card is not None and logger:
                        logger.event("program_card", **program_card.as_dict())
                # host-side, no sync
                frames_real_ctr.inc(batch.frames_real)
                frames_padded_ctr.inc(batch.frames_padded)
                if trace_active and step - start_step >= profile_steps[1]:
                    with obs.Span("profile_stop", registry=registry,
                                  events=events_log, dir=profile_dir,
                                  step=step):
                        jax.block_until_ready(losses["total_loss"])
                        jax.profiler.stop_trace()
                    trace_active = False
                if plan.fire("sigterm", step):
                    fault_ctr.inc()
                    if logger:
                        logger.event("fault_fire", kind="sigterm", step=step)
                    faults.deliver_sigterm()

                if step % steps.log_step == 0:
                    # host boundary: the loop blocks here for logging anyway,
                    # so the sentinel read adds no extra sync point. The
                    # drain IS device time the async dispatches above
                    # deferred: the event's step_time_s is dispatch + sync.
                    with obs.Span("train_sync", registry=registry):
                        jax.block_until_ready(losses["total_loss"])
                        finite = "_finite" not in losses or bool(losses["_finite"])
                        _count_routing(registry, moe_pending, run_ctx)
                    if not finite:
                        n = guard.trip(step)  # raises past max_rollbacks
                        ckpt.wait()
                        good = ckpt.latest_step()
                        rollback_ctr.inc()
                        msg = (
                            f"[resilience] non-finite losses/grads at step "
                            f"{step}; rollback {n}/{res.max_rollbacks} to "
                            + (f"checkpoint step {good}" if good is not None
                               else "fresh init (no checkpoint yet)")
                        )
                        print(msg)
                        if logger:
                            logger.note(msg)
                            logger.event(
                                "rollback", step=step, rollback_n=n,
                                restore_step=good,
                            )
                        prefetch.stop()
                        if good is not None:
                            state = ckpt.restore(abstract_template, step=good)
                        else:
                            state = fresh_state()
                        step = int(state.step)  # jaxlint: disable=JL004
                        prefetch = make_stream(guard.count)
                        window_t0, window_step0 = time.monotonic(), step
                        window_totals = _window_totals(registry, lm, diffusion)
                        continue
                    t_log = time.monotonic()
                    with obs.Span("train_log", registry=registry):
                        guard.ok()
                        watermark = obs.device_memory_watermark(program_card)
                        if watermark is not None:
                            mem_gauge.set(watermark)
                        for dev, wm in obs.device_memory_watermarks(
                            program_card, devices=mesh_devices
                        ).items():
                            registry.gauge(
                                "device_memory_watermark_bytes",
                                labels={"device": dev},
                                help="per-device memory watermark (backend "
                                     "memory_stats peak, else ProgramCard "
                                     "argument+temp bytes)",
                            ).set(wm)
                        if logger:
                            contracts.assert_tree_finite(
                                public_losses(losses), "train_step.losses"
                            )
                            lr = float(schedule(jnp.asarray(step - 1)))
                            n_window = step - window_step0
                            dt = t_log - window_t0
                            timing = None
                            if n_window > 0:
                                totals = _window_totals(registry, lm, diffusion)
                                timing = {
                                    k: (totals[k] - window_totals[k]) / n_window
                                    for k in totals
                                }
                                timing["step_time_s"] = (
                                    timing["dispatch_s"] + timing["sync_s"]
                                )
                                window_totals = totals
                                if dt > 0:
                                    timing["steps_per_sec"] = n_window / dt
                                    timing["mel_frames_per_sec"] = (
                                        timing["frames_real"] * n_window / dt
                                    )
                            logger.log(
                                step,
                                {k: float(v)
                                 for k, v in public_losses(losses).items()},
                                lr=lr,
                                timing=timing,
                            )
                            if timing and "steps_per_sec" in timing:
                                logger.log_throughput(
                                    step, timing["steps_per_sec"],
                                    timing["mel_frames_per_sec"],
                                )
                            window_t0, window_step0 = t_log, step
                if synth_callback is not None and step % steps.synth_step == 0:
                    synth_callback(state, batch, arrays, step, model)
                if step % steps.val_step == 0:
                    with DevicePrefetcher(
                        val_batcher.epoch(shuffle=False), mesh=mesh,
                        registry=val_registry,
                    ) as val_prefetch:
                        val_losses = evaluate(eval_step, state, val_prefetch)
                    # evaluate() already returns host floats
                    last_val = val_losses.get("total_loss", last_val)
                    if logger:
                        logger.log(step, val_losses, prefix="val")
                if step % steps.save_step == 0:
                    ckpt.save(step, state, val_loss=last_val)
                    save_ctr.inc()
                    if logger:
                        logger.event("checkpoint_save", step=step)
                    last_saved = step

            # always flush a final checkpoint: covers total_step not
            # divisible by save_step AND the SIGTERM/SIGINT preemption path
            if step > start_step and last_saved != step:
                ckpt.save(step, state, val_loss=last_val, block=True)
                save_ctr.inc()
                if logger:
                    logger.event("checkpoint_save", step=step, final=True)
                last_saved = step
            if shutdown.requested:
                msg = (
                    f"[resilience] {shutdown.signame}: checkpoint flushed at "
                    f"step {step}; exiting"
                )
                print(msg)
                if logger:
                    logger.note(msg)
                    logger.event(
                        "preempt_flush", signal=shutdown.signame, step=step
                    )
            if logger:
                # the run's set-up cost, readable without the process: how
                # many programs it compiled, how long that took, and how
                # many came out of the persistent cache (warm vs cold)
                logger.event(
                    "train_end", step=step,
                    cache_dir=program_registry.cache_dir,
                    **obs.jaxmon.compile_totals(registry),
                )
    finally:
        compiles.close()
        if trace_active:
            jax.profiler.stop_trace()  # run ended inside the profile window
        prefetch.stop()
        if quarantine.bad and logger:
            logger.note(
                f"[resilience] {len(quarantine.bad)} quarantined sample(s): "
                f"{sorted(quarantine.bad)}"
            )
            logger.event("quarantine", samples=sorted(quarantine.bad))
        if logger:
            logger.close()
        ckpt.close()
    return state


class TrainLogger:
    """TensorBoard scalars/figures/audio + append-only log.txt (reference:
    train.py:53-61, utils/tools.py:82-107). tensorboardX is optional; the
    text log always works.

    With ``registry``/``events`` attached (obs/), every ``log()`` call
    also updates the metric gauges and appends one structured JSONL
    record (``train_step``/``val`` — schema in obs/events.py), so the
    human-readable log and the machine-readable telemetry cannot drift:
    they are written by the same call from the same values.
    """

    def __init__(self, log_dir: str, use_tensorboard: bool = True,
                 registry: Optional[obs.MetricsRegistry] = None,
                 events: Optional[obs.JsonlEventLog] = None):
        os.makedirs(log_dir, exist_ok=True)
        self.txt = open(os.path.join(log_dir, "log.txt"), "a")
        self.registry = registry
        self.events = events
        self.tb = None
        if use_tensorboard:
            try:
                from tensorboardX import SummaryWriter

                self.tb = SummaryWriter(log_dir)
            except ImportError:
                pass

    def log(self, step: int, losses: Dict[str, float],
            lr: Optional[float] = None, prefix: str = "train",
            timing: Optional[Dict[str, float]] = None):
        msg = f"[{prefix}] Step {step}, " + ", ".join(
            f"{k}: {float(v):.4f}" for k, v in losses.items()
        )
        if lr is not None:
            msg += f", lr: {lr:.6f}"
        self.txt.write(msg + "\n")
        self.txt.flush()
        if self.tb is not None:
            for k, v in losses.items():
                self.tb.add_scalar(f"{prefix}/{k}", float(v), step)
            if lr is not None:
                self.tb.add_scalar(f"{prefix}/lr", lr, step)
        if self.registry is not None:
            self.registry.gauge("train_step", help="last logged step").set(step)
            for k, v in losses.items():
                # values arrive as host floats (the caller converts at the
                # log boundary); Gauge.set coerces, no device sync here
                self.registry.gauge(
                    "train_loss", labels={"loss": k, "split": prefix}
                ).set(v)
        self.event(
            "train_step" if prefix == "train" else prefix,
            step=step,
            **{k: float(v) for k, v in losses.items()},
            **({"lr": lr} if lr is not None else {}),
            **(timing or {}),
        )

    def event(self, name: str, /, **fields):
        """Append one structured record to events.jsonl (no-op without an
        event log attached). ``name`` is positional-only so records may
        themselves carry a ``name`` field (program cards do)."""
        if self.events is not None:
            self.events.emit(name, **fields)

    def note(self, msg: str):
        """Raw line into log.txt (resilience events: rollbacks, SIGTERM
        flushes, quarantine summaries) — greppable next to the step log."""
        self.txt.write(msg + "\n")
        self.txt.flush()
        self.event("note", msg=msg)

    def log_throughput(self, step: int, steps_per_sec: float, frames_per_sec: float):
        self.txt.write(
            f"[perf] Step {step}, steps/s: {steps_per_sec:.2f}, "
            f"mel-frames/s: {frames_per_sec:.0f}\n"
        )
        self.txt.flush()
        if self.tb is not None:
            self.tb.add_scalar("perf/steps_per_sec", steps_per_sec, step)
            self.tb.add_scalar("perf/mel_frames_per_sec", frames_per_sec, step)

    def log_figure(self, step: int, tag: str, fig):
        if self.tb is not None:
            self.tb.add_figure(tag, fig, step)

    def log_audio(self, step: int, tag: str, wav, sampling_rate: int,
                  max_wav_value: float = 32768.0):
        if self.tb is not None:
            import numpy as np

            wav = np.asarray(wav, np.float32) / max_wav_value
            try:
                self.tb.add_audio(tag, wav[None], step, sample_rate=sampling_rate)
            except ModuleNotFoundError:
                pass  # tensorboardX audio needs soundfile; scalars/figures still log

    def close(self):
        self.txt.close()
        if self.events is not None:
            self.events.close()
        if self.tb is not None:
            self.tb.close()


def default_synth_callback(cfg: Config, logger: Optional[TrainLogger], vocoder=None):
    """Periodic validation-sample rendering (reference: train.py:117-144):
    plot GT-vs-predicted mel and log both vocoded wavs to TensorBoard."""

    def callback(state, batch, arrays, step, model):
        from speakingstyle_tpu.synthesis import synth_one_sample

        out = model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            **_model_kwargs(arrays, teacher_forced=True),
            deterministic=True,
        )
        fig, wav_recon, wav_pred, basename = synth_one_sample(
            batch, out, vocoder, cfg
        )
        if logger is not None:
            sr = cfg.preprocess.preprocessing.audio.sampling_rate
            mw = cfg.preprocess.preprocessing.audio.max_wav_value
            logger.log_figure(step, f"Training/{basename}", fig)
            logger.log_audio(
                step, f"Training/{basename}_reconstructed", wav_recon, sr, mw
            )
            logger.log_audio(
                step, f"Training/{basename}_synthesized", wav_pred, sr, mw
            )
        import matplotlib.pyplot as plt

        plt.close(fig)

    return callback
