#!/usr/bin/env python3
"""First light on the chip: train and serve the flagship through the normal
entry points, and check what comes out.

    python chip_smoke.py

drives ``python -m speakingstyle_tpu train`` (8 steps, batch 48, one
``(src 128, mel 640)`` bucket) and ``python -m speakingstyle_tpu serve``
(single engine, real HiFi-GAN generator, 8-point lattice incl. the mel-1000
bucket) at the full width of the ``LJSpeech_paper`` preset with seeded
random weights, plus a kernel leg that checks the fused-MHA Pallas kernel
against its einsum reference; the train and serve legs prove the kernel is
IN their compiled programs (``mosaic_calls`` on the program cards). Exit 0 and
a last stdout line ``{"ok": true, "device": {...}}`` only if every leg
passed on a TPU; anything else — no accelerator, a child's non-zero exit, a
timeout, a missing event — is a non-zero exit that names the leg.

Process shape: this parent never imports jax (a parent that touched JAX
would hold the chip its children need). The legs run as child processes,
one at a time; all share the persistent compile cache that
``obs/jaxmon.enable_compilation_cache`` places. Everything the legs read is
generated from seeds under ``.chip_smoke/`` next to this file: corpus,
lexicon, reference wav and the three YAMLs, derived from the preset with
only paths, the ``step:`` block and the ``serve:`` lattice changed.

The numbers it prints (wall seconds, compile seconds, cache hits, step and
request milliseconds) are first-light observations for CHANGES.md — one
run, compile included, no warm-up discipline — NOT benchmark numbers.

``tests/test_chip_smoke.py`` rehearses the same code on the CPU at a tiny
size (``run_legs(TINY, ...)``), so a typo costs no chip time.
"""

import argparse
import dataclasses
import importlib.util
import json
import math
import os
import shutil
import signal
import socket
import struct
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, ".chip_smoke")
PRESET_DIR = os.path.join(
    ROOT, "speakingstyle_tpu", "configs", "presets", "LJSpeech_paper"
)
# the contract allows 1200 s, compilation included; keep a margin for the
# interpreter start-ups and the final report
TOTAL_BUDGET_S = 1140.0
SEED = 1234


class LegFailed(Exception):
    """One leg of the smoke failed; ``leg`` names it in the verdict."""

    def __init__(self, leg: str, reason: str):
        super().__init__(f"{leg}: {reason}")
        self.leg = leg
        self.reason = reason


@dataclasses.dataclass(frozen=True)
class Size:
    """Everything that differs between the chip run and its CPU rehearsal."""

    name: str
    on_chip: bool
    # None = the preset's model.yaml, byte for byte
    model: Optional[dict]
    n_utts: int
    val_utts: int
    n_phones_per_utt: Tuple[int, int]
    duration_range: Tuple[int, int]
    batch_size: int
    max_steps: int
    log_step: int
    batch_buckets: Tuple[int, ...]
    src_buckets: Tuple[int, ...]
    mel_buckets: Tuple[int, ...]
    ref_buckets: Tuple[int, ...]
    # serve.* keys beyond the lattice that the size forces (none at full size)
    serve_extra: dict
    # frames per phoneme pinned into the served checkpoint
    pinned_frames: int
    # phoneme counts: three sequential requests, four concurrent, one stream
    sequential: Tuple[int, int, int]
    concurrent: Tuple[int, int, int, int]
    stream: int
    ref_seconds: float
    # (B, H, T, D, backward too?) for the fused_mha checks
    kernel_shapes: Tuple[Tuple[int, int, int, int, bool], ...]
    # (B, T, Cin, Cout, K) for the one pallas_conv forward
    conv_shape: Tuple[int, int, int, int, int]


# The flagship: LJSpeech_paper widths, training at B=48, 100 phonemes and
# 600 frames
# (scripts/train_descent.py: 97-104 phones x 5-7 frames => every batch in
# ONE (src 128, mel 640) bucket, ~29k mel frames per step of 48), and the
# serve lattice cut in count, not width: the mel-1000 bucket pads to the
# kernel's MAX_T = 1024 and stays on purpose.
FULL = Size(
    name="full", on_chip=True, model=None,
    n_utts=480, val_utts=48, n_phones_per_utt=(97, 104), duration_range=(5, 7),
    batch_size=48, max_steps=8, log_step=2,
    batch_buckets=(1, 4), src_buckets=(64, 128), mel_buckets=(512, 1000),
    ref_buckets=(512,), serve_extra={},
    pinned_frames=6,
    # serve.frames_per_phoneme (12) sizes the output buffer: 24 phonemes
    # ride (src 64, mel 512), 56 ride (64, 1000), 80 ride (128, 1000)
    sequential=(24, 56, 80), concurrent=(28, 29, 30, 31), stream=40,
    ref_seconds=4.0,
    kernel_shapes=(
        (48, 8, 640, 32, True),    # reference encoder, train geometry
        (48, 2, 640, 128, True),   # encoder/decoder, train geometry
        (4, 2, 1000, 128, False),  # serve lattice's largest bucket
    ),
    conv_shape=(48, 640, 256, 1024, 9),
)

# CPU rehearsal: the tiny widths of tests/test_synthesis.py::
# test_cli_train_smoke, a two-point lattice, kernels in interpret mode.
TINY = Size(
    name="tiny", on_chip=False,
    model={
        "transformer": {"encoder_layer": 1, "decoder_layer": 1,
                        "encoder_hidden": 32, "decoder_hidden": 32,
                        "conv_filter_size": 64},
        "reference_encoder": {"encoder_layer": 1, "encoder_hidden": 32,
                              "conv_filter_size": 64},
        "variance_predictor": {"filter_size": 32},
        "variance_embedding": {"n_bins": 16},
        "max_seq_len": 96,
    },
    n_utts=20, val_utts=4, n_phones_per_utt=(8, 12), duration_range=(2, 4),
    batch_size=4, max_steps=2, log_step=1,
    batch_buckets=(1, 4), src_buckets=(32,), mel_buckets=(128,),
    ref_buckets=(96,), serve_extra={"frames_per_phoneme": 4},
    pinned_frames=2,
    sequential=(6, 14, 24), concurrent=(8, 9, 10, 11), stream=20,
    ref_seconds=0.8,
    kernel_shapes=((2, 2, 24, 16, True),),
    conv_shape=(2, 24, 16, 32, 3),
)

SIZES = {s.name: s for s in (FULL, TINY)}


# ---------------------------------------------------------------------------
# inputs, all from seeds (parent side: numpy + yaml, no jax)
# ---------------------------------------------------------------------------


def _synthetic_module():
    """``speakingstyle_tpu/data/synthetic.py`` loaded by path: the module is
    numpy-only, but importing it through its package would import jax."""
    path = os.path.join(ROOT, "speakingstyle_tpu", "data", "synthetic.py")
    spec = importlib.util.spec_from_file_location("_smoke_synthetic", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _lexicon_words(phones: List[str]) -> List[str]:
    """One pseudo-word per phone, so a text of n words is exactly n
    phonemes and the parent knows every request's length without G2P."""
    return ["w" + "".join(chr(97 + d) for d in divmod(i, 26))
            for i in range(len(phones))]


def make_text(n_phonemes: int, words: List[str], seed: int) -> str:
    import numpy as np

    rng = np.random.default_rng(seed)
    return " ".join(words[i] for i in rng.integers(0, len(words), n_phonemes))


def write_ref_wav(path: str, seconds: float, sampling_rate: int) -> None:
    """A seeded harmonic tone with a slow envelope, 16-bit mono PCM."""
    import wave

    import numpy as np

    rng = np.random.default_rng(SEED)
    t = np.arange(int(seconds * sampling_rate)) / sampling_rate
    f0 = rng.uniform(110.0, 220.0)
    wav = sum(a * np.sin(2 * np.pi * f0 * k * t + rng.uniform(0, 2 * np.pi))
              for k, a in ((1, 0.6), (2, 0.25), (3, 0.1)))
    wav = wav * 0.5 * (1.0 + np.sin(2 * np.pi * 1.5 * t)) * 0.5
    with wave.open(path, "wb") as f:
        f.setnchannels(1)
        f.setsampwidth(2)
        f.setframerate(sampling_rate)
        f.writeframes((wav * 32767).astype("<i2").tobytes())


def generate_inputs(size: Size, out_dir: str) -> Dict:
    """Corpus, lexicon, reference wav and the three YAMLs under ``out_dir``
    (emptied first). Returns the paths and facts the legs need."""
    import yaml

    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(os.path.join(out_dir, "logs"))
    synthetic = _synthetic_module()
    corpus = synthetic.generate_corpus(
        os.path.join(out_dir, "corpus"),
        n_utts=size.n_utts, val_utts=size.val_utts,
        n_phones_per_utt=size.n_phones_per_utt,
        duration_range=size.duration_range, seed=SEED,
    )
    words = _lexicon_words(synthetic.PHONES)
    lexicon = os.path.join(out_dir, "lexicon.txt")
    with open(lexicon, "w") as f:
        for word, phone in zip(words, synthetic.PHONES):
            f.write(f"{word} {phone}\n")

    def preset(name):
        with open(os.path.join(PRESET_DIR, name)) as f:
            return yaml.safe_load(f)

    pre = preset("preprocess.yaml")
    pre["path"]["preprocessed_path"] = corpus
    pre["path"]["lexicon_path"] = lexicon
    trn = preset("train.yaml")
    trn["path"] = {
        "ckpt_path": os.path.join(out_dir, "ckpt"),
        "log_path": os.path.join(out_dir, "log"),
        "result_path": os.path.join(out_dir, "result"),
    }
    # one validation pass at the last step; the only save is the final flush
    trn["step"] = {
        "total_step": size.max_steps, "log_step": size.log_step,
        "val_step": size.max_steps, "save_step": 10 ** 6,
        "synth_step": 10 ** 6,
    }
    trn["optimizer"]["batch_size"] = size.batch_size
    trn["serve"] = {
        "batch_buckets": list(size.batch_buckets),
        "src_buckets": list(size.src_buckets),
        "mel_buckets": list(size.mel_buckets),
        "style": {"ref_buckets": list(size.ref_buckets)},
        **size.serve_extra,
    }
    cfg_dir = os.path.join(out_dir, "cfg")
    os.makedirs(cfg_dir)
    paths = {k: os.path.join(cfg_dir, f"{k}.yaml")
             for k in ("preprocess", "model", "train")}
    with open(paths["preprocess"], "w") as f:
        yaml.safe_dump(pre, f)
    with open(paths["train"], "w") as f:
        yaml.safe_dump(trn, f)
    if size.model is None:
        shutil.copyfile(os.path.join(PRESET_DIR, "model.yaml"), paths["model"])
    else:
        with open(paths["model"], "w") as f:
            yaml.safe_dump(size.model, f)

    audio = pre["preprocessing"]["audio"]
    ref_wav = os.path.join(out_dir, "ref.wav")
    write_ref_wav(ref_wav, size.ref_seconds, audio["sampling_rate"])
    return {
        "out_dir": out_dir, "paths": paths, "ref_wav": ref_wav,
        "words": words, "log_path": trn["path"]["log_path"],
        "sampling_rate": audio["sampling_rate"],
        "hop_length": pre["preprocessing"]["stft"]["hop_length"],
    }


def config_args(paths: Dict) -> List[str]:
    return ["-p", paths["preprocess"], "-m", paths["model"],
            "-t", paths["train"]]


# ---------------------------------------------------------------------------
# child processes
# ---------------------------------------------------------------------------


class Children:
    """Starts the legs' processes and guarantees none outlives the smoke:
    each child leads its own process group, killed on ``close``."""

    def __init__(self, out_dir: str, deadline: float):
        self.out_dir = out_dir
        self.deadline = deadline
        self._live: List[subprocess.Popen] = []

    def log_path(self, leg: str) -> str:
        return os.path.join(self.out_dir, "logs", f"{leg}.log")

    def remaining(self, leg: str) -> float:
        left = self.deadline - time.monotonic()
        if left <= 0:
            raise LegFailed(leg, "the smoke's total time budget is spent")
        return left

    def start(self, leg: str, cmd: List[str]) -> subprocess.Popen:
        self.remaining(leg)
        log = open(self.log_path(leg), "w")
        try:
            proc = subprocess.Popen(
                cmd, cwd=ROOT, stdout=log, stderr=subprocess.STDOUT,
                start_new_session=True,
                env={**os.environ, "PYTHONUNBUFFERED": "1"},
            )
        finally:
            log.close()  # the child holds its own descriptor
        self._live.append(proc)
        return proc

    def wait(self, leg: str, proc: subprocess.Popen, cap_s: float) -> None:
        """Block until ``proc`` exits 0; anything else fails the leg."""
        try:
            rc = proc.wait(timeout=min(cap_s, self.remaining(leg)))
        except subprocess.TimeoutExpired:
            self.kill(proc)
            raise LegFailed(
                leg, f"timed out\n{self.tail(leg)}") from None
        if rc != 0:
            raise LegFailed(leg, f"exit code {rc}\n{self.tail(leg)}")

    def run(self, leg: str, cmd: List[str], cap_s: float) -> float:
        """Run one child to completion; returns its wall seconds."""
        t0 = time.monotonic()
        self.wait(leg, self.start(leg, cmd), cap_s)
        return time.monotonic() - t0

    def tail(self, leg: str, n: int = 40) -> str:
        with open(self.log_path(leg), errors="replace") as f:
            return "".join(f.readlines()[-n:])

    @staticmethod
    def kill(proc: subprocess.Popen) -> None:
        if proc.poll() is None:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()

    def close(self) -> None:
        for proc in self._live:
            self.kill(proc)


def child_cmd(mode: str, size: Size, inputs: Dict) -> List[str]:
    return [sys.executable, os.path.join(ROOT, "chip_smoke.py"),
            "--child", mode, "--size", size.name,
            "--out", inputs["out_dir"], *config_args(inputs["paths"])]


def read_json(path: str, leg: str) -> Dict:
    if not os.path.exists(path):
        raise LegFailed(leg, f"left no result file {path}")
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# kernel leg (child side: imports jax)
# ---------------------------------------------------------------------------


def _device_report() -> Dict:
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _versions() -> Dict:
    import platform
    from importlib import metadata

    out = {"python": platform.python_version()}
    for pkg in ("jax", "jaxlib", "libtpu", "flax", "optax",
                "orbax-checkpoint", "numpy"):
        try:
            out[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def _check_fused_mha(B, H, T, D, backward, interpret, dtype_name) -> Dict:
    """fused_mha against _reference_mha on the same rounded inputs.

    Tolerances, relative to max|reference|. The reference runs in float32
    on the kernel's own (bf16-rounded) inputs, so what differs is the
    kernel's internal rounding. Forward: probabilities are rounded to the
    compute dtype before the PV matmul and the output is stored in it —
    two roundings of at most 2^-9 each in bf16 (8 significand bits); 2^-7
    leaves 4x for the different summation order. Backward chains four such
    roundings (p, ds, the incoming cotangent, the stored dq/dk/dv) through
    one more matmul: 2^-6. First measured on a v5e: 0.3% forward, 0.25%
    backward.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from speakingstyle_tpu.ops.pallas_attention import (
        _reference_mha,
        fused_mha,
    )
    from speakingstyle_tpu.parallel.registry import jit_program

    dtype = jnp.dtype(dtype_name)
    rng = np.random.default_rng(B + H + T + D)
    q, k, v = (jnp.asarray(rng.standard_normal((B, T, H, D)), dtype)
               for _ in range(3))
    lens = rng.integers(T // 2, T + 1, B)
    mask = jnp.asarray(np.arange(T)[None] >= lens[:, None])
    real = jnp.where(mask, 0.0, 1.0)[:, :, None, None]
    scale = 1.0 / math.sqrt(D)
    f32 = lambda x: x.astype(jnp.float32)

    def fused(q_, k_, v_):
        return f32(fused_mha(q_, k_, v_, mask, interpret=interpret)) * real

    def reference(q_, k_, v_):
        return _reference_mha(
            f32(q_), f32(k_), f32(v_), mask, scale, jnp.float32) * real

    def rel_err(got, want):
        return float(jnp.max(jnp.abs(f32(got) - want))
                     / jnp.max(jnp.abs(want)))

    def grads(f):
        return jit_program(jax.grad(
            lambda *a: jnp.sum(jnp.square(f(*a))), argnums=(0, 1, 2)))

    out = {"shape": [B, H, T, D], "dtype": dtype_name,
           "fwd_rel_err": rel_err(jit_program(fused)(q, k, v),
                                  jit_program(reference)(q, k, v)),
           "fwd_tol": 2.0 ** -7}
    ok = out["fwd_rel_err"] <= out["fwd_tol"]
    if backward:
        got = grads(fused)(q, k, v)
        want = grads(reference)(q, k, v)
        out["bwd_rel_err"] = [rel_err(g, w) for g, w in zip(got, want)]
        out["bwd_tol"] = 2.0 ** -6
        ok = ok and max(out["bwd_rel_err"]) <= out["bwd_tol"]
    out["ok"] = bool(ok)
    return out


def _try_pallas_conv(B, T, cin, cout, K, interpret, dtype_name) -> Dict:
    """One forward of the off-by-default ``conv_impl: pallas`` kernel. Its
    outcome is recorded, never gating (ROADMAP C3 wants the path gone): so
    this is the one place a failure is caught and written down."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from speakingstyle_tpu.ops.pallas_conv import fused_conv1d

    dtype = jnp.dtype(dtype_name)
    rng = np.random.default_rng(K)
    x = jnp.asarray(rng.standard_normal((B, T, cin)), dtype)
    w = jnp.asarray(rng.standard_normal((K, cin, cout)) * 0.02, dtype)
    b = jnp.zeros((cout,), dtype)
    try:
        y = jax.block_until_ready(
            fused_conv1d(x, w, b, relu=True, interpret=interpret))
    except Exception as e:  # recorded outcome, see docstring
        return {"compiled": False,
                "error": f"{type(e).__name__}: {str(e)[:400]}"}
    ref = jnp.maximum(jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32), (1,), "SAME",
        dimension_numbers=("NWC", "WIO", "NWC")), 0.0)
    return {"compiled": True, "shape": [B, T, cin, cout, K],
            "rel_err": float(jnp.max(jnp.abs(y.astype(jnp.float32) - ref))
                             / jnp.max(jnp.abs(ref)))}


def child_kernel(size: Size, args) -> int:
    device = _device_report()
    print("device:", json.dumps(device), flush=True)
    if size.on_chip and device["platform"] != "tpu":
        print("device guard: JAX found no accelerator (platform "
              f"{device['platform']!r}); the chip smoke proves nothing on "
              "a CPU", flush=True)
        return 3

    from speakingstyle_tpu.configs.config import load_config
    from speakingstyle_tpu.obs import MetricsRegistry, watch_compiles
    from speakingstyle_tpu.obs.jaxmon import (
        compile_totals,
        enable_compilation_cache,
    )

    registry = MetricsRegistry()
    watch_compiles(registry)
    result = {"device": device, "versions": _versions(),
              "cache_dir": enable_compilation_cache()}
    cfg = load_config(preprocess=args.preprocess_config,
                      model=args.model_config, train=args.train_config)
    interpret = not size.on_chip
    dtype = cfg.model.compute_dtype
    result["fused_mha"] = [
        _check_fused_mha(*shape, interpret=interpret, dtype_name=dtype)
        for shape in size.kernel_shapes
    ]
    result["pallas_conv"] = _try_pallas_conv(
        *size.conv_shape, interpret=interpret, dtype_name=dtype)
    result.update(compile_totals(registry))
    with open(os.path.join(args.out, "kernel.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0


def check_kernel(result: Dict) -> None:
    for check in result["fused_mha"]:
        if not check["ok"]:
            raise LegFailed("kernel", f"fused_mha off its reference: {check}")


# ---------------------------------------------------------------------------
# pin leg (child side): make the served checkpoint speak
# ---------------------------------------------------------------------------


def child_pin(size: Size, args) -> int:
    """Restore the train leg's checkpoint, pin the duration predictor
    (data/synthetic.pin_durations — eight steps do not teach one, and a
    model that predicts zero frames vocodes nothing) and save it as the next
    step through CheckpointManager.save, manifest and digest included, so
    ``serve --restore_step -1`` restores what the normal save path wrote."""
    import jax
    import jax.numpy as jnp

    from speakingstyle_tpu.configs.config import load_config
    from speakingstyle_tpu.data.synthetic import pin_durations
    from speakingstyle_tpu.models.factory import build_model, init_variables
    from speakingstyle_tpu.obs import MetricsRegistry, watch_compiles
    from speakingstyle_tpu.obs.jaxmon import (
        compile_totals,
        enable_compilation_cache,
    )
    from speakingstyle_tpu.training.checkpoint import CheckpointManager
    from speakingstyle_tpu.training.optim import make_optimizer
    from speakingstyle_tpu.training.state import TrainState

    enable_compilation_cache()
    registry = MetricsRegistry()
    watch_compiles(registry)
    cfg = load_config(preprocess=args.preprocess_config,
                      model=args.model_config, train=args.train_config)
    model = build_model(cfg)
    variables = init_variables(model, cfg, jax.random.PRNGKey(cfg.train.seed))
    state = TrainState.create(variables, make_optimizer(cfg.train))
    ckpt = CheckpointManager(cfg.train.path.ckpt_path)
    try:
        state = ckpt.restore(state)
        restored = ckpt.last_restored_step
        pinned = pin_durations({"params": state.params}, size.pinned_frames)
        state = state.replace(
            step=state.step + 1,
            params=jax.tree_util.tree_map(jnp.asarray, pinned["params"]),
        )
        ckpt.save(restored + 1, state, block=True)
    finally:
        ckpt.close()
    result = {"device": _device_report(), "restored_step": restored,
              "saved_step": restored + 1, **compile_totals(registry)}
    with open(os.path.join(args.out, "pin.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result), flush=True)
    return 0


# ---------------------------------------------------------------------------
# train leg checks (parent side)
# ---------------------------------------------------------------------------


def read_events(log_path: str) -> List[Dict]:
    # the repo's own reader (rotation, torn tails); obs/ imports no jax
    from speakingstyle_tpu.obs.events import read_events as read

    events = list(read(log_path))
    if not events:
        raise LegFailed("train", f"no events under {log_path}")
    return events


def check_train_events(events: List[Dict], size: Size,
                       backend: str) -> Dict:
    """The train leg's pass conditions, read from ``events.jsonl``."""

    def of(kind):
        return [e for e in events if e.get("event") == kind]

    def fail(reason):
        raise LegFailed("train", reason)

    starts = of("train_start")
    if len(starts) != 1:
        fail(f"expected one train_start event, found {len(starts)}")
    start = starts[0]
    if start.get("backend") != backend:
        fail(f"train_start.backend is {start.get('backend')!r}, "
             f"expected {backend!r}")
    steps = of("train_step")
    want_steps = list(range(size.log_step, size.max_steps + 1, size.log_step))
    if [e.get("step") for e in steps] != want_steps:
        fail(f"train_step records at steps {[e.get('step') for e in steps]}, "
             f"expected {want_steps}")
    for e in steps:
        losses = {k: v for k, v in e.items() if k.endswith("loss")}
        if "total_loss" not in losses or not all(
                isinstance(v, float) and math.isfinite(v)
                for v in losses.values()):
            fail(f"non-finite or missing losses at step {e['step']}: {losses}")
    first, last = steps[0]["total_loss"], steps[-1]["total_loss"]
    if not last < first:
        fail(f"loss did not fall: {first} at step {steps[0]['step']} -> "
             f"{last} at step {steps[-1]['step']}")
    cards = of("program_card")
    if len(cards) != 1 or cards[0].get("partial"):
        fail(f"expected one non-partial program_card, found {cards}")
    if backend == "tpu" and not cards[0].get("mosaic_calls"):
        # the fused kernel must be IN the step the trainer compiled, not
        # the einsum path the model takes when the backend is not a TPU
        fail("the train step's program card counts no tpu_custom_call: "
             "attention took the einsum path")
    if not any(e.get("final") for e in of("checkpoint_save")):
        fail("no checkpoint_save event with final: true")
    if len(of("val")) != 1:
        fail(f"expected one validation pass, found {len(of('val'))}")
    ends = of("train_end")
    if len(ends) != 1:
        fail(f"expected one train_end event, found {len(ends)}")
    end = ends[0]
    return {
        "backend": start["backend"],
        "device_kind": start.get("device_kind"),
        "device_count": start.get("device_count"),
        "loss_first": first, "loss_last": last,
        "val_total_loss": of("val")[0].get("total_loss"),
        "card_flops": cards[0].get("flops"),
        "mosaic_calls": cards[0].get("mosaic_calls"),
        # the last window's mean: compile-free from the second window on
        "steady_step_ms": 1e3 * steps[-1]["step_time_s"],
        "mel_frames_per_step": (
            steps[-1]["mel_frames_per_sec"] / steps[-1]["steps_per_sec"]),
        "cache_dir": end["cache_dir"],
        **{k: end[k] for k in ("compiles", "compile_seconds", "cache_hits",
                               "cache_requests")},
    }


# ---------------------------------------------------------------------------
# serve leg (parent side: urllib only)
# ---------------------------------------------------------------------------


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(base: str, path: str, payload: Optional[Dict] = None,
         timeout: float = 120.0) -> Tuple[int, Dict, bytes]:
    """GET, or POST of a JSON payload. Returns (status, headers, body); an
    HTTP error status is a return value here, judged by the caller."""
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        base + path, data=data,
        headers={"Content-Type": "application/json"} if data else {},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, dict(resp.headers), resp.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def wait_ready(base: str, proc: subprocess.Popen, children: Children,
               cap_s: float) -> None:
    """Poll /healthz until 200; the server answers nothing before its
    lattice is precompiled, so a refused connection means "not yet"."""
    t_end = time.monotonic() + min(cap_s, children.remaining("serve"))
    while time.monotonic() < t_end:
        if proc.poll() is not None:
            raise LegFailed("serve", f"server exited {proc.returncode} before "
                            f"it was ready\n{children.tail('serve')}")
        try:
            if http(base, "/healthz", timeout=5.0)[0] == 200:
                return
        except (urllib.error.URLError, ConnectionError, socket.timeout):
            pass
        time.sleep(1.0)
    raise LegFailed("serve", "no /healthz 200 in time\n"
                    f"{children.tail('serve')}")


def metric(text: str, name: str) -> float:
    """Sum of one metric family's samples in Prometheus text."""
    values = [float(line.rsplit(" ", 1)[1]) for line in text.splitlines()
              if line.startswith(name) and line[len(name)] in " {"]
    if not values:
        raise LegFailed("serve", f"/metrics exports no {name}")
    return sum(values)


def pcm_from_wav(body: bytes, sampling_rate: int):
    """int16 samples of a 44-byte-header mono PCM wav; the streaming
    variant carries 0xFFFFFFFF length fields, so lengths are not read."""
    import numpy as np

    if (len(body) < 44 or body[:4] != b"RIFF" or body[8:12] != b"WAVE"
            or body[36:40] != b"data"):
        raise LegFailed("serve", f"not a wav body: {body[:60]!r}")
    channels, rate = struct.unpack("<HI", body[22:28])
    bits, = struct.unpack("<H", body[34:36])
    if (channels, rate, bits) != (1, sampling_rate, 16):
        raise LegFailed("serve", f"wav is {channels}ch {rate}Hz {bits}bit")
    return np.frombuffer(body[44:], "<i2")


def check_audio(what: str, status: int, body: bytes, n_phonemes: int,
                size: Size, inputs: Dict) -> Dict:
    """A 200 carrying PCM that is not silence and is exactly as long as
    the pinned durations imply."""
    if status != 200:
        raise LegFailed("serve", f"{what}: HTTP {status}: {body[:300]!r}")
    pcm = pcm_from_wav(body, inputs["sampling_rate"])
    want = size.pinned_frames * n_phonemes * inputs["hop_length"]
    if len(pcm) != want:
        raise LegFailed(
            "serve", f"{what}: {len(pcm)} samples, expected {want} "
            f"({n_phonemes} phonemes x {size.pinned_frames} frames x hop "
            f"{inputs['hop_length']})")
    # random generator weights are quiet (about 10 LSB rms on the CPU), but
    # a vocoder that ran on real frames never returns a constant
    std = float(pcm.astype("float64").std())
    if not std >= 1.0:
        raise LegFailed("serve", f"{what}: silent PCM (std {std:.3f} LSB)")
    return {"samples": len(pcm), "std_lsb": round(std, 2)}


def post_text(base: str, path: str, n_phonemes: int, inputs: Dict,
              seed: int) -> Tuple[int, Dict, bytes, float]:
    text = make_text(n_phonemes, inputs["words"], seed)
    t0 = time.monotonic()
    status, headers, body = http(base, path, {"text": text})
    return status, headers, body, 1e3 * (time.monotonic() - t0)


def concurrent_volley(base: str, size: Size, inputs: Dict,
                      seed: int) -> List[Tuple]:
    """Four POSTs released by one barrier, so all are admitted inside the
    batcher's coalescing window."""
    barrier = threading.Barrier(len(size.concurrent))
    results: List = [None] * len(size.concurrent)

    def client(i, n):
        barrier.wait(timeout=30)
        results[i] = post_text(base, "/synthesize", n, inputs, seed + i)

    threads = [threading.Thread(target=client, args=(i, n))
               for i, n in enumerate(size.concurrent)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    if any(t.is_alive() for t in threads) or None in results:
        raise LegFailed("serve", "a concurrent client never returned")
    return results


def drive_server(base: str, size: Size, inputs: Dict, backend: str) -> Dict:
    """The request script: 3 sequential, 4 concurrent, 1 streamed; zero
    compiles between the first and the last."""
    status, _, body = http(base, "/healthz")
    health = json.loads(body)
    if status != 200 or health["build"].get("backend") != backend:
        raise LegFailed("serve", f"/healthz {status}, build.backend "
                        f"{health.get('build', {}).get('backend')!r}, "
                        f"expected {backend!r}")
    counters = ("serve_compiles_total", "serve_style_compiles_total",
                "jax_backend_compiles_total")
    before_text = http(base, "/metrics")[2].decode()
    before = {name: metric(before_text, name) for name in counters}
    out: Dict = {"build": health["build"],
                 "lattice_points": health["lattice_points"],
                 "compiles_at_ready": before, "requests": []}

    def record(kind, i, n, response, **extra):
        """Judge one response and note it; returns its headers."""
        status, headers, body, ms = response
        audio = check_audio(f"{kind} request {i}", status, body, n, size,
                            inputs)
        out["requests"].append({"kind": kind, "phonemes": n,
                                "ms": round(ms, 1), **extra, **audio})
        return headers

    for i, n in enumerate(size.sequential):
        record("sequential", i, n,
               post_text(base, "/synthesize", n, inputs, SEED + i))

    # the batcher coalesces what is admitted within serve.max_wait_ms of
    # the first arrival; a volley whose threads were scheduled apart
    # splits, so up to three volleys may be sent — every response of
    # every volley must still be valid audio
    for attempt in range(1, 4):
        volley = concurrent_volley(base, size, inputs, SEED + 100 * attempt)
        rows = [
            int(record("concurrent", i, n, response, volley=attempt)
                .get("X-Batch-Rows", 0))
            for i, (n, response) in enumerate(zip(size.concurrent, volley))
        ]
        if rows == [len(size.concurrent)] * len(size.concurrent):
            out["coalesce_attempts"] = attempt
            break
    else:
        raise LegFailed("serve", "four concurrent requests never coalesced "
                        f"into one batch-4 dispatch in 3 volleys: {rows}")

    record("stream", 0, size.stream,
           post_text(base, "/synthesize/stream", size.stream, inputs,
                     SEED + 7))

    after_text = http(base, "/metrics")[2].decode()
    after = {name: metric(after_text, name) for name in counters}
    if after != before:
        raise LegFailed("serve", "a compile happened in steady state: "
                        f"{before} before the first request, {after} after "
                        "the last")
    out["compile_seconds"] = metric(
        after_text, "jax_backend_compile_seconds_total")
    out["cache_hits"] = metric(after_text, "jax_persistent_cache_hits_total")
    out["cache_requests"] = metric(
        after_text, "jax_persistent_cache_requests_total")
    programs = json.loads(http(base, "/debug/programs")[2])["programs"]
    out["programs"] = [
        {k: p.get(k) for k in ("name", "compile_seconds", "mosaic_calls")}
        for p in programs
    ]
    einsum = [p["name"] for p in out["programs"]
              if p["name"].startswith("acoustic") and not p["mosaic_calls"]]
    if backend == "tpu" and einsum:
        # every bucket of the lattice, mel 1000 included, pads inside the
        # kernel's MAX_T: none may have taken the einsum path
        raise LegFailed("serve", "acoustic programs without a "
                        f"tpu_custom_call: {einsum}")
    return out


def run_serve_leg(size: Size, inputs: Dict, children: Children,
                  backend: str) -> Dict:
    port = free_port()
    base = f"http://127.0.0.1:{port}"
    t0 = time.monotonic()
    proc = children.start("serve", [
        sys.executable, "-m", "speakingstyle_tpu", "serve",
        *config_args(inputs["paths"]), "--restore_step", "-1",
        "--ref_audio", inputs["ref_wav"], "--port", str(port),
    ])
    wait_ready(base, proc, children, cap_s=700.0)
    ready_s = time.monotonic() - t0
    out = drive_server(base, size, inputs, backend)
    out["ready_seconds"] = ready_s
    os.killpg(proc.pid, signal.SIGTERM)
    children.wait("serve", proc, cap_s=90.0)  # exit code 0, or the leg fails
    log = children.tail("serve", n=10 ** 6)
    if "draining" not in log:
        raise LegFailed("serve", "SIGTERM left no 'draining' line\n"
                        + children.tail("serve"))
    precompiled = [ln for ln in log.splitlines()
                   if ln.startswith("precompiled ")]
    out["precompile_line"] = precompiled[-1] if precompiled else None
    out["wall_seconds"] = time.monotonic() - t0
    return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def run_legs(size: Size, out_dir: str,
             budget_s: float = TOTAL_BUDGET_S) -> Dict:
    """All legs in order, one process at a time; raises LegFailed on the
    first that fails. Returns the report."""
    children = Children(out_dir, time.monotonic() + budget_s)
    report: Dict = {"size": size.name}
    try:
        t0 = time.monotonic()
        inputs = generate_inputs(size, out_dir)
        report["generate_seconds"] = time.monotonic() - t0

        wall = children.run(
            "kernel", child_cmd("kernel", size, inputs), cap_s=420.0)
        kernel = read_json(os.path.join(out_dir, "kernel.json"), "kernel")
        check_kernel(kernel)
        report["kernel"] = {"wall_seconds": wall, **kernel}
        backend = kernel["device"]["platform"]

        wall = children.run("train", [
            sys.executable, "-m", "speakingstyle_tpu", "train",
            *config_args(inputs["paths"]),
            "--max_steps", str(size.max_steps),
            "--data_parallel", "1", "--model_parallel", "1",
        ], cap_s=600.0)
        report["train"] = {"wall_seconds": wall, **check_train_events(
            read_events(inputs["log_path"]), size, backend)}

        wall = children.run(
            "pin", child_cmd("pin", size, inputs), cap_s=240.0)
        pin = read_json(os.path.join(out_dir, "pin.json"), "pin")
        if pin["restored_step"] != size.max_steps:
            raise LegFailed("pin", f"restored step {pin['restored_step']}, "
                            f"the train leg ended at {size.max_steps}")
        report["pin"] = {"wall_seconds": wall, **pin}

        report["serve"] = run_serve_leg(size, inputs, children, backend)
    finally:
        children.close()
    return report


def print_report(report: Dict) -> None:
    k, t, p, s = (report[x] for x in ("kernel", "train", "pin", "serve"))
    print("first-light observations (one run, compile included; NOT "
          "benchmark numbers)")
    print(f"  device    {json.dumps(k['device'])}")
    print(f"  versions  {json.dumps(k['versions'])}")
    print(f"  cache dir {k['cache_dir']}")

    def leg(name, d, extra):
        print(f"  {name:<7} wall {d['wall_seconds']:6.1f}s  compiling "
              f"{d['compile_seconds']:6.1f}s  cache hits/requests "
              f"{d['cache_hits']:.0f}/{d['cache_requests']:.0f}  {extra}")

    leg("kernel", k,
        "fused_mha rel err "
        + ", ".join(f"{c['shape']}: fwd {c['fwd_rel_err']:.2e}"
                    + (f" bwd {max(c['bwd_rel_err']):.2e}"
                       if "bwd_rel_err" in c else "")
                    for c in k["fused_mha"])
        + f"; pallas_conv {json.dumps(k['pallas_conv'])}")
    leg("train", t,
        f"loss {t['loss_first']:.3f} -> {t['loss_last']:.3f}, steady step "
        f"{t['steady_step_ms']:.1f} ms for {t['mel_frames_per_step']:.0f} "
        f"mel frames, {t['mosaic_calls']} tpu_custom_call in the step, "
        f"backend {t['backend']} ({t['device_kind']} x{t['device_count']})")
    leg("pin", p, f"step {p['restored_step']} -> {p['saved_step']}")
    by_kind: Dict[str, List[float]] = {}
    for prog in s["programs"]:
        by_kind.setdefault(prog["name"].split(":")[0], []).append(
            prog["compile_seconds"])
    leg("serve", s,
        f"ready in {s['ready_seconds']:.1f}s; {s['precompile_line']}; "
        "seconds per program "
        + ", ".join(f"{kind} {sum(v) / len(v):.1f} (x{len(v)})"
                    for kind, v in by_kind.items())
        + "; tpu_custom_call per acoustic program "
        + str(sorted({p["mosaic_calls"] for p in s["programs"]
                      if p["name"].startswith("acoustic")}))
        + f"; coalesced on volley {s['coalesce_attempts']}")
    for r in s["requests"]:
        print(f"            {r['kind']:<10} {r['phonemes']:3d} phonemes "
              f"{r['ms']:8.1f} ms  {r['samples']} samples  "
              f"std {r['std_lsb']} LSB"
              + (f"  volley {r['volley']}" if "volley" in r else ""))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--child", choices=("kernel", "pin"), default=None,
                    help="internal: run one leg's child body")
    ap.add_argument("--size", choices=sorted(SIZES), default="full")
    ap.add_argument("--out", default=OUT_DIR)
    ap.add_argument("-p", dest="preprocess_config")
    ap.add_argument("-m", dest="model_config")
    ap.add_argument("-t", dest="train_config")
    args = ap.parse_args(argv)
    if args.child:
        body = {"kernel": child_kernel, "pin": child_pin}[args.child]
        return body(SIZES[args.size], args)

    # the parent always runs the full size: a pass means the flagship ran
    # on a TPU (the kernel leg's device guard refuses anything else)
    try:
        report = run_legs(FULL, OUT_DIR)
    except LegFailed as e:
        print(f"chip_smoke FAILED in the {e.leg} leg: {e.reason}",
              file=sys.stderr)
        return 1
    with open(os.path.join(OUT_DIR, "report.json"), "w") as f:
        json.dump(report, f, indent=1)
    print_report(report)
    print(json.dumps({"ok": True, "device": report["kernel"]["device"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
