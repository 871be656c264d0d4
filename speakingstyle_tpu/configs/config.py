"""Typed, validated configuration system.

Replaces the reference's raw-YAML-triple plumbing (reference:
train.py:176-200 passes three untyped dicts positionally) with frozen
dataclasses. The three-file split (preprocess/model/train) and per-dataset
presets are preserved so reference configs remain readable, but every key is
schema-checked at load time — the config-drift crashes catalogued in
SURVEY.md §2.5 become load-time errors here.
"""

from __future__ import annotations

import dataclasses
import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import yaml

PRESET_DIR = os.path.join(os.path.dirname(__file__), "presets")


def _build(cls, data: Dict[str, Any], path: str = ""):
    """Recursively build a dataclass from a nested dict, rejecting unknown keys."""
    if data is None:
        data = {}
    import typing

    hints = typing.get_type_hints(cls)
    names = {f.name for f in dataclasses.fields(cls)}
    unknown = set(data) - names
    if unknown:
        raise ValueError(f"Unknown config keys at {path or cls.__name__}: {sorted(unknown)}")
    kwargs = {}
    for name in names:
        if name not in data:
            continue
        value = data[name]
        ftype = hints.get(name)
        if dataclasses.is_dataclass(ftype) and isinstance(value, dict):
            value = _build(ftype, value, f"{path}.{name}" if path else name)
        kwargs[name] = value
    return cls(**kwargs)


# ---------------------------------------------------------------------------
# preprocess.yaml
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class PathConfig:
    corpus_path: str = ""
    lexicon_path: str = ""
    raw_path: str = ""
    preprocessed_path: str = ""


@dataclass(frozen=True)
class TextConfig:
    text_cleaners: List[str] = field(default_factory=lambda: ["english_cleaners"])
    language: str = "en"


@dataclass(frozen=True)
class AudioConfig:
    sampling_rate: int = 22050
    max_wav_value: float = 32768.0


@dataclass(frozen=True)
class STFTConfig:
    filter_length: int = 1024
    hop_length: int = 256
    win_length: int = 1024


@dataclass(frozen=True)
class MelConfig:
    n_mel_channels: int = 80
    mel_fmin: float = 0.0
    mel_fmax: Optional[float] = 8000.0


@dataclass(frozen=True)
class VarianceFeatureConfig:
    feature: str = "phoneme_level"  # or "frame_level"
    normalization: bool = True

    def __post_init__(self):
        if self.feature not in ("phoneme_level", "frame_level"):
            raise ValueError(f"feature must be phoneme_level|frame_level, got {self.feature}")


@dataclass(frozen=True)
class PreprocessingConfig:
    val_size: int = 512
    text: TextConfig = field(default_factory=TextConfig)
    audio: AudioConfig = field(default_factory=AudioConfig)
    stft: STFTConfig = field(default_factory=STFTConfig)
    mel: MelConfig = field(default_factory=MelConfig)
    pitch: VarianceFeatureConfig = field(default_factory=VarianceFeatureConfig)
    energy: VarianceFeatureConfig = field(default_factory=VarianceFeatureConfig)


@dataclass(frozen=True)
class PreprocessConfig:
    dataset: str = "LJSpeech"
    path: PathConfig = field(default_factory=PathConfig)
    preprocessing: PreprocessingConfig = field(default_factory=PreprocessingConfig)


# ---------------------------------------------------------------------------
# model.yaml
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class TransformerConfig:
    encoder_layer: int = 4
    encoder_head: int = 2
    encoder_hidden: int = 256
    decoder_layer: int = 6
    decoder_head: int = 2
    decoder_hidden: int = 256
    conv_filter_size: int = 1024
    conv_kernel_size: Tuple[int, int] = (9, 1)
    encoder_dropout: float = 0.2
    decoder_dropout: float = 0.2


@dataclass(frozen=True)
class ReferenceEncoderConfig:
    encoder_layer: int = 4
    encoder_head: int = 8
    encoder_hidden: int = 256
    conv_layer: int = 3
    conv_filter_size: int = 1024
    conv_kernel_size: int = 3
    dropout: float = 0.1


@dataclass(frozen=True)
class VariancePredictorConfig:
    filter_size: int = 256
    kernel_size: int = 3
    dropout: float = 0.5


@dataclass(frozen=True)
class VarianceEmbeddingConfig:
    pitch_quantization: str = "linear"  # "linear" | "log"
    energy_quantization: str = "linear"
    n_bins: int = 256

    def __post_init__(self):
        for q in (self.pitch_quantization, self.energy_quantization):
            if q not in ("linear", "log"):
                raise ValueError(f"quantization must be linear|log, got {q}")


@dataclass(frozen=True)
class VocoderConfig:
    model: str = "HiFi-GAN"
    speaker: str = "LJSpeech"


@dataclass(frozen=True)
class RopeConfig:
    """One layer type's rotary positions, keys as a Hugging Face
    ``rope_parameters`` group has them. ``default``: inverse frequencies
    ``theta^(-2k/d)``. ``yarn``: those blended with the same over ``factor``
    along the linear ramp between the two correction dimensions
    (``beta_fast``, ``beta_slow`` rotations over
    ``original_max_position_embeddings``), cos and sin times
    ``attention_factor``."""

    rope_type: str = "default"
    rope_theta: float = 500000.0
    factor: float = 1.0
    original_max_position_embeddings: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def __post_init__(self):
        if self.rope_type not in ("default", "yarn"):
            raise ValueError(f"rope_type must be default|yarn, got {self.rope_type}")


@dataclass(frozen=True)
class RopeParametersConfig:
    full_attention: RopeConfig = field(default_factory=lambda: RopeConfig(
        rope_type="yarn", factor=16.0, attention_factor=1.2772588722239782))
    sliding_attention: RopeConfig = field(default_factory=RopeConfig)


@dataclass(frozen=True)
class DecoderLMConfig:
    """The ``decoder_lm`` family (models/mellum.py): a pre-norm decoder of
    RMSNorm, grouped-query rotary attention (a window or the full causal
    triangle, by layer) and a sparse-expert feed-forward, trained on
    next-token cross-entropy or (``objective: block_diffusion``) to fill in
    a noised block of ``block_length`` tokens given the clean blocks before
    it. The keys a published ``config.json`` has keep
    its names and defaults (Mellum2-12B-A2.5B); the rest say what the
    objective is, what this chip
    holds of an expert-parallel deployment and what a row of the data is.
    ``layer_types`` may be longer than ``num_hidden_layers`` (a depth cut
    keeps the published list whole and runs its head)."""

    model_type: str = "mellum"
    vocab_size: int = 98304
    hidden_size: int = 2304
    intermediate_size: int = 7168   # dense layers' width: none here, unused
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    hidden_act: str = "silu"
    attention_bias: bool = False
    layer_types: List[str] = field(default_factory=lambda: [
        "sliding_attention", "sliding_attention", "sliding_attention",
        "full_attention"] * 7)
    mlp_layer_types: List[str] = field(default_factory=lambda: ["sparse"] * 28)
    sliding_window: int = 1024
    use_sliding_window: bool = True
    max_window_layers: int = 0
    max_position_embeddings: int = 131072
    rms_norm_eps: float = 1e-6
    num_experts: int = 64           # the router's outputs: all of the model's
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    norm_topk_prob: bool = True
    tie_word_embeddings: bool = False
    rope_parameters: RopeParametersConfig = field(
        default_factory=RopeParametersConfig)
    # -- the objective -------------------------------------------------------
    # "next_token", or "block_diffusion": every row runs as a noised copy and
    # the clean copy side by side (2 * seq_len positions) under one
    # block-structured mask, and the loss is the weighted cross-entropy of
    # the masked positions of the noised half (models/mellum.py). The loader
    # draws the noise: ``mask_id`` stands where a token is masked.
    objective: str = "next_token"
    block_length: int = 4
    mask_id: int = 0
    qk_norm: bool = False           # RMSNorm over each head's query and key
    # -- what this chip holds (0: everything) --------------------------------
    expert_offset: int = 0          # the first expert held here
    experts_held: int = 0           # how many, from expert_offset on
    vocab_held: int = 0             # rows of embedding and head: ids < this
    # -- the data's rows -----------------------------------------------------
    seq_len: int = 8192             # positions of a packed row
    eod_id: int = 0                 # closes a document inside a row

    def __post_init__(self):
        n = self.num_hidden_layers
        if len(self.layer_types) < n or len(self.mlp_layer_types) < n:
            raise ValueError(
                f"layer_types/mlp_layer_types list {len(self.layer_types)}/"
                f"{len(self.mlp_layer_types)} layers, num_hidden_layers is {n}")
        for kind in self.layer_types[:n]:
            if kind not in ("sliding_attention", "full_attention"):
                raise ValueError(f"unknown layer type {kind!r}")
        if any(k != "sparse" for k in self.mlp_layer_types[:n]):
            raise ValueError("only sparse feed-forward layers are built")
        if self.hidden_act != "silu" or self.attention_bias \
                or self.tie_word_embeddings:
            raise ValueError("decoder_lm builds silu experts, no attention "
                             "bias and an untied head")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must be a multiple of "
                             "num_key_value_heads")
        held = self.experts_held or self.num_experts
        if self.expert_offset < 0 or self.expert_offset + held > self.num_experts:
            raise ValueError(
                f"experts [{self.expert_offset}, {self.expert_offset + held}) "
                f"are not among the router's {self.num_experts}")
        if not 0 <= self.vocab_held <= self.vocab_size:
            raise ValueError("vocab_held must lie within vocab_size")
        if self.objective not in ("next_token", "block_diffusion"):
            raise ValueError("objective must be next_token|block_diffusion, "
                             f"got {self.objective}")
        if self.block_diffusion:
            if self.block_length < 1 or self.seq_len % self.block_length:
                raise ValueError(f"rows of {self.seq_len} tokens are no whole "
                                 f"number of blocks of {self.block_length}")
            if not 0 <= self.mask_id < self.n_vocab_held:
                raise ValueError("mask_id must be a held vocabulary row")
            if any(k != "full_attention" for k in self.layer_types[:n]):
                raise ValueError("block diffusion runs full_attention layers")

    @property
    def block_diffusion(self) -> bool:
        return self.objective == "block_diffusion"

    @property
    def n_experts_held(self) -> int:
        return self.experts_held or self.num_experts

    @property
    def n_vocab_held(self) -> int:
        return self.vocab_held or self.vocab_size


@dataclass(frozen=True)
class ModelConfig:
    # which model the factory builds: "acoustic" (FastSpeech2 with the style
    # reference encoder: every block below but decoder_lm) or "decoder_lm"
    # (the decoder_lm block alone, with compute_dtype)
    family: str = "acoustic"
    decoder_lm: DecoderLMConfig = field(default_factory=DecoderLMConfig)
    transformer: TransformerConfig = field(default_factory=TransformerConfig)
    reference_encoder: ReferenceEncoderConfig = field(default_factory=ReferenceEncoderConfig)
    variance_predictor: VariancePredictorConfig = field(default_factory=VariancePredictorConfig)
    variance_embedding: VarianceEmbeddingConfig = field(default_factory=VarianceEmbeddingConfig)
    multi_speaker: bool = False
    max_seq_len: int = 1000
    vocoder: VocoderConfig = field(default_factory=VocoderConfig)
    # postnet topology (reference hardcodes 512/5/5 — model/modules.py);
    # exposed so scaled-down configs (tests, the distilled student) shrink
    # the whole model, not all-but-the-postnet
    postnet_embedding_dim: int = 512
    postnet_kernel_size: int = 5
    postnet_layers: int = 5
    # TPU-specific knobs (no reference counterpart):
    compute_dtype: str = "bfloat16"  # activations/matmul dtype under jit
    # conv1d lowering for the FLOP-dominant conv stacks (ops/conv.py):
    # "xla" = lax.conv emitter, "unfold" = im2col GEMM (one large MXU
    # matmul per conv), "pallas" = fused conv+bias+ReLU(+LN) kernel
    # (ops/pallas_conv.py). Param trees are identical — switchable on a
    # restored checkpoint. The default is what the benchmark runs
    # (train_ljspeech_b200; the decoder_lm family has no such site).
    # It was chosen over "unfold" and "pallas" by a comparison read on
    # an earlier installation; no reading in PERF_LEDGER.jsonl (ROADMAP
    # C2).
    conv_impl: str = "xla"
    # softmax accumulation dtype in attention: "float32" (reference-parity
    # default) or "bfloat16" (A/B candidate; attention is <1% of step
    # FLOPs so this mostly saves VPU/memory traffic)
    attention_softmax_dtype: str = "float32"
    use_reference_encoder: bool = True
    # attention lowering for the dense path: "fused" (default, what the
    # benchmark runs — ops/pallas_attention.py: one VMEM pass per
    # (batch, head), f32 softmax in-register; its lead over "einsum" was
    # read on an earlier installation; no reading in PERF_LEDGER.jsonl
    # (ROADMAP C2))
    # or "einsum" (XLA, materializes [B, H, L, L] scores in HBM — the
    # literal transcription of the reference math). "fused" compiles the
    # kernel on a TPU backend and takes the einsum path on any other (CPU
    # tests and parity runs always exercise einsum numerics) and for
    # L > 1024; on a TPU a head dim the kernel cannot tile raises at
    # trace time rather than falling back silently. Parameter-free, so
    # switchable on a restored checkpoint.
    # Sharding: the kernel carries a custom_partitioning batch rule —
    # without it GSPMD ALL-GATHERS the operands of a custom call.
    # Validated: zero all-gathers + batch-sharded grads in the
    # 8-device-mesh HLO
    # (tests/test_parallel.py::test_fused_attention_batch_partitioned_*),
    # loss parity with einsum under the data-sharded train step, and
    # hardware execution on the 1-chip mesh (chip_smoke.py, both cells).
    attention_kernel: str = "fused"
    # "dense" or "ring": ring engages sequence-parallel exact attention
    # (parallel/ring_attention.py) in the encoder/decoder FFT stacks for
    # inference beyond max_seq_len — build the model with a seq mesh
    # (models/factory.build_model(..., seq_mesh=...)); sequence lengths
    # must divide by the mesh's seq axis.
    attention_impl: str = "dense"
    # dropout mask generation (ops/dropout.py): "hash" (default, what
    # the benchmark runs — salted murmur3 counter hash, pure
    # elementwise so XLA fuses it into the consumer; zero RNG-bit HBM
    # traffic), "bernoulli" (jax.random, what nn.Dropout does — the
    # reference-parity RNG stream), or "bits16" (raw 16-bit threshold
    # compare). The order hash < bernoulli < bits16 in cost was read on
    # an earlier installation; no reading in PERF_LEDGER.jsonl (ROADMAP
    # C2). Mask distribution is identical across impls (inverted
    # dropout, P(keep)=1-rate); only the PRNG stream differs, so this is
    # switchable on a restored checkpoint.
    dropout_impl: str = "hash"

    def __post_init__(self):
        if self.family not in ("acoustic", "decoder_lm"):
            raise ValueError(
                f"model family must be acoustic|decoder_lm, got {self.family}")
        if self.attention_impl not in ("dense", "ring"):
            raise ValueError(
                f"attention_impl must be dense|ring, got {self.attention_impl}"
            )
        if self.dropout_impl not in ("bernoulli", "bits16", "hash"):
            raise ValueError(
                f"dropout_impl must be bernoulli|bits16|hash, "
                f"got {self.dropout_impl}"
            )
        if self.conv_impl not in ("xla", "unfold", "pallas"):
            raise ValueError(
                f"conv_impl must be xla|unfold|pallas, got {self.conv_impl}"
            )
        if self.attention_kernel not in ("einsum", "fused"):
            raise ValueError(
                f"attention_kernel must be einsum|fused, got {self.attention_kernel}"
            )
        if self.attention_softmax_dtype not in ("float32", "bfloat16"):
            raise ValueError(
                "attention_softmax_dtype must be float32|bfloat16, "
                f"got {self.attention_softmax_dtype}"
            )
        if self.attention_impl == "ring" and self.attention_softmax_dtype != "float32":
            # the ring path accumulates its running softmax in f32 by design
            # (parallel/ring_attention.py); a bf16 label would misreport A/Bs
            raise ValueError(
                'attention_impl="ring" supports only '
                'attention_softmax_dtype="float32"'
            )


# ---------------------------------------------------------------------------
# train.yaml
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class OptimizerConfig:
    batch_size: int = 16
    betas: Tuple[float, float] = (0.9, 0.98)
    eps: float = 1e-9
    weight_decay: float = 0.0
    grad_clip_thresh: float = 1.0
    grad_acc_step: int = 1
    warm_up_step: int = 4000  # vestigial in the reference; kept for config parity
    anneal_steps: List[int] = field(default_factory=lambda: [300000, 400000, 500000])
    anneal_rate: float = 0.3
    init_lr: float = 1e-4
    anneal_lr: float = 1e-3


@dataclass(frozen=True)
class StepConfig:
    total_step: int = 900000
    log_step: int = 100
    synth_step: int = 1000
    val_step: int = 1000
    save_step: int = 1000


@dataclass(frozen=True)
class LossConfig:
    lambda_f: float = 0.0  # FiLM-gate L2 weight (reference: model/loss.py:20,84-89)
    anneal_steps: int = 10000  # LR ramp length (reference: model/optimizer.py:17,37-44)


@dataclass(frozen=True)
class TrainPathConfig:
    ckpt_path: str = "./output/ckpt"
    log_path: str = "./output/log"
    result_path: str = "./output/result"


@dataclass(frozen=True)
class ShardingConfig:
    """TPU mesh layout (no reference counterpart; replaces nn.DataParallel).

    Legacy block: ``train.parallel`` (ParallelConfig) is the multichip
    contract now; this survives for old YAML and the
    ``--data_parallel``/``--model_parallel`` CLI flags, which map onto the
    same mesh resolution in ``cli/train.py``."""

    data_axis: int = -1  # -1: all devices on the data axis
    model_axis: int = 1  # tensor-parallel degree (1 = pure DP)
    remat: bool = False  # jax.checkpoint the FFT stacks


@dataclass(frozen=True)
class ParallelConfig:
    """Multichip mesh layout (``parallel/mesh.py`` /
    ``parallel/partition.py`` — ARCHITECTURE.md "Multichip training").
    Used twice: ``train.parallel`` shapes the trainer's mesh,
    ``serve.parallel`` shapes one serving replica's mesh slice.

    ``mesh = [dp, tp]`` names the 2-D device mesh: batches shard over the
    ``data`` axis (dp-way), parameters shard over the ``model`` axis
    (tp-way, Megatron-style column/row rules). The default ``[1, 1]`` is
    the single-chip path — ``resolve_mesh`` returns ``None`` and the
    trainer behaves exactly as before. ``dp = -1`` consumes all devices
    not claimed by ``tp``.
    """

    # [dp, tp]: data-parallel x tensor-parallel degree. [1, 1] = single
    # chip (mesh path disengaged); dp = -1 = all remaining devices
    mesh: List[int] = field(default_factory=lambda: [1, 1])
    # sequence-parallel axis for ring attention (long-context training);
    # 1 = off. Engages attention_impl="ring" semantics; the mesh then
    # needs dp*tp*seq devices.
    seq: int = 1
    # partition-rule overrides PREPENDED to DEFAULT_TP_RULES (first match
    # wins): each entry is [path_regex, axes] where axes is a
    # comma-separated per-dim list of mesh axis names or "none", e.g.
    # ["encoder_emb/embedding$", "none,model"] -> P(None, "model")
    partition_rules: List[List[str]] = field(default_factory=list)

    def __post_init__(self):
        if len(self.mesh) != 2:
            raise ValueError(
                f"parallel.mesh must be [dp, tp], got {self.mesh}"
            )
        dp, tp = self.mesh
        if tp < 1:
            raise ValueError(f"parallel.mesh tp must be >= 1, got {tp}")
        if dp < 1 and dp != -1:
            raise ValueError(
                f"parallel.mesh dp must be >= 1 (or -1 for all "
                f"remaining devices), got {dp}"
            )
        if self.seq < 1:
            raise ValueError(f"parallel.seq must be >= 1, got {self.seq}")
        import re as _re

        for rule in self.partition_rules:
            if len(rule) != 2 or not all(isinstance(s, str) for s in rule):
                raise ValueError(
                    "parallel.partition_rules entries must be "
                    f"[path_regex, axes] string pairs, got {rule!r}"
                )
            pattern, axes = rule
            try:
                _re.compile(pattern)
            except _re.error as e:
                raise ValueError(
                    f"parallel.partition_rules regex {pattern!r}: {e}"
                )
            for tok in axes.split(","):
                if tok.strip().lower() not in ("", "none", "data", "model", "seq"):
                    raise ValueError(
                        f"parallel.partition_rules axes token {tok!r} "
                        "must be one of none|data|model|seq"
                    )

    @property
    def dp(self) -> int:
        return self.mesh[0]

    @property
    def tp(self) -> int:
        return self.mesh[1]

    def is_single(self) -> bool:
        """True iff this config keeps the single-chip train path."""
        return tuple(self.mesh) == (1, 1) and self.seq == 1


@dataclass(frozen=True)
class ResilienceConfig:
    """Fault-tolerance knobs (training/resilience.py — no reference
    counterpart; the reference loop dies on the first bad sample and
    loses up to save_step steps on preemption).

    See the "Resilience" section of ARCHITECTURE.md for the fault model
    and the ``SPEAKINGSTYLE_FAULTS`` injection spec grammar."""

    # checkpoint saves run on a background thread (the step loop never
    # blocks on Orbax I/O); the device->host snapshot is still taken
    # synchronously so buffer donation cannot invalidate an in-flight save
    async_checkpointing: bool = True
    # retain the newest N step checkpoints; 0 keeps everything
    max_to_keep: int = 5
    # never prune the best-val-loss step, even past max_to_keep
    keep_best: bool = True
    # fold an all-finite reduction over losses+grads into the jitted step
    # and check it host-side at the log boundary; on trip, roll back to
    # the last good checkpoint with a diverged data stream
    nan_sentinel: bool = True
    # abort with TrainingDivergedError after this many CONSECUTIVE
    # rollbacks (a finite check window resets the counter)
    max_rollbacks: int = 3
    # feature-loader retry-with-exponential-backoff on transient I/O errors
    loader_retries: int = 3
    loader_backoff: float = 0.05  # seconds; doubles per attempt
    # samples that still fail after retries are quarantined (logged +
    # skipped); the run fails only past this many distinct bad samples
    bad_sample_budget: int = 16

    def __post_init__(self):
        if self.max_to_keep < 0:
            raise ValueError(f"max_to_keep must be >= 0, got {self.max_to_keep}")
        if self.max_rollbacks < 0:
            raise ValueError(
                f"max_rollbacks must be >= 0, got {self.max_rollbacks}"
            )
        if self.loader_retries < 0:
            raise ValueError(
                f"loader_retries must be >= 0, got {self.loader_retries}"
            )
        if self.bad_sample_budget < 0:
            raise ValueError(
                f"bad_sample_budget must be >= 0, got {self.bad_sample_budget}"
            )


@dataclass(frozen=True)
class ObsConfig:
    """Telemetry knobs (speakingstyle_tpu/obs/ — ARCHITECTURE.md
    "Observability"). The metrics registry itself is always on (it is
    just in-memory counters); these control the export surfaces."""

    # rotating JSONL event log under train.path.log_path (obs/events.py
    # documents the schema; read it with `python -m speakingstyle_tpu.obs.cli`)
    events: bool = True
    # rotation: shift events.jsonl -> .1 past this size, keep N rotated files
    events_max_bytes: int = 8_000_000
    events_keep: int = 3
    # persistent XLA compilation cache directory, an explicit override
    # honoured only when JAX_COMPILATION_CACHE_DIR is unset; "" = the
    # fixed <checkout>/.jax_cache (obs/jaxmon.enable_compilation_cache
    # owns the choice). The jaxmon bridge counts cache hits vs requests
    # per-registry (jax_persistent_cache_{hits,requests}_total) so
    # /metrics distinguishes a warm start from a cold one
    compilation_cache_dir: str = ""
    # build a ProgramCard for the jitted train step after its first
    # compile (obs/cost.py): emits a one-time `program_card` JSONL event
    # and feeds the achieved-FLOP/s histogram + device-memory watermark.
    # Built right after the step's first jit call, so its AOT compile
    # is served from jax's in-memory executable cache (no second
    # compile observed on the chip, PR 21)
    program_card: bool = True

    def __post_init__(self):
        if self.events_max_bytes <= 0:
            raise ValueError(
                f"events_max_bytes must be > 0, got {self.events_max_bytes}"
            )
        if self.events_keep < 1:
            raise ValueError(f"events_keep must be >= 1, got {self.events_keep}")


@dataclass(frozen=True)
class TrainConfig:
    path: TrainPathConfig = field(default_factory=TrainPathConfig)
    optimizer: OptimizerConfig = field(default_factory=OptimizerConfig)
    step: StepConfig = field(default_factory=StepConfig)
    loss: LossConfig = field(default_factory=LossConfig)
    sharding: ShardingConfig = field(default_factory=ShardingConfig)
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)
    ignore_layers: List[str] = field(default_factory=list)
    seed: int = 1234
    # Use XLA's native RBG bit generator for dropout masks instead of
    # threefry (dropout masks over [B,600,1024] tensors dominate
    # threefry's generation cost). True is what both benchmark cells
    # run; its gain was read on an earlier installation; no reading in
    # PERF_LEDGER.jsonl (ROADMAP C2). No reference counterpart (torch
    # RNG is cuRAND); disable for bit-stable dropout streams across
    # hardware.
    fast_prng: bool = True
    # Run clip+Adam+LR as one fused pass over a single raveled parameter
    # vector (training/optim.py make_fused_optimizer) instead of the
    # per-leaf optax chain: mathematically identical update (parity test
    # in tests/test_training.py), different opt_state layout (flat mu/nu),
    # so checkpoints are not interchangeable with the unfused optimizer.
    # False (the optax chain) is what both benchmark cells run; "flat"
    # was slower than the chain when read on an earlier installation; no
    # reading in PERF_LEDGER.jsonl (ROADMAP C2).
    # "leaf" (training/optim.make_leaf_fused_optimizer): the whole
    # clip+L2+Adam+lr chain as ONE fused expression per param leaf — no
    # ravel copies, no per-stage intermediate trees. True == "flat" for
    # back-compat. All three impls produce bit-identical updates (parity
    # test); opt_state layouts differ, so optimizer checkpoints are not
    # interchangeable across impls.
    fused_optimizer: object = False  # False | True | "flat" | "leaf"

    def __post_init__(self):
        if self.fused_optimizer not in (False, True, "flat", "leaf"):
            raise ValueError(
                "fused_optimizer must be False|True|'flat'|'leaf', "
                f"got {self.fused_optimizer!r}"
            )


# ---------------------------------------------------------------------------
# serve.* — the synthesis server (serving/; no reference counterpart)
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class FleetConfig:
    """Multi-replica fleet serving knobs (serving/fleet.py,
    serving/streaming.py — ARCHITECTURE.md "Fleet serving & streaming").

    The fleet router runs N replica engines behind one SLO-aware
    admission queue: requests carry a priority class, the queue orders by
    earliest SLO deadline (EDF), and queue-depth watermarks shed load
    with HTTP 429 + Retry-After well before the queue hard-fills —
    distinct from shutdown rejection (``serve_shed_total`` vs
    ``serve_rejected_total``).
    """

    # replica engines behind the router (one per device, or N on one
    # device for the CPU proxy); `cli serve --replicas N` overrides
    replicas: int = 1
    # bounded pending heap the router admits into (EDF-ordered); all
    # serving queues are bounded — backpressure is meaningless otherwise
    # (jaxlint JL011 enforces this structurally for queue.Queue)
    queue_depth: int = 256
    # load-shedding hysteresis as fractions of queue_depth: shedding
    # starts when pending >= high * depth and stops once it drains to
    # <= low * depth (two watermarks so the 429 boundary cannot flap
    # request-by-request)
    shed_high_watermark: float = 0.9
    shed_low_watermark: float = 0.5
    # Retry-After seconds advertised on a 429 shed response
    shed_retry_after_s: float = 1.0
    # priority classes: request "priority" -> SLO completion budget (ms);
    # the router's EDF heap orders by arrival + this budget
    class_deadline_ms: Dict[str, float] = field(
        default_factory=lambda: {"interactive": 250.0, "batch": 2000.0}
    )
    default_class: str = "interactive"
    # chunked streaming synthesis: emit wav in windows of this many mel
    # frames (POST /synthesize/stream); windows ride the precompiled
    # vocoder lattice buckets, never ad-hoc shapes
    stream_window: int = 64
    # mel-frame context vocoded on each side of a window and trimmed
    # from the emitted wav; 0 = derive from the vocoder's receptive
    # field (streaming.receptive_field_frames), which is the smallest
    # overlap that keeps chunk seams bit-exact
    stream_overlap: int = 0
    # vocoder windows in flight per stream: window k+1 is dispatched
    # before window k is collected (JAX async dispatch), so steady-state
    # chunk cadence is max(device window, host trim+emit) instead of
    # their sum; 1 = strictly sequential (the pre-pipeline behavior,
    # bit-identical output)
    stream_depth: int = 2
    # SIGTERM/shutdown waits this long for in-flight streams to finish
    drain_timeout_s: float = 10.0
    # --- resilience (serving/resilience.py, ARCHITECTURE.md "Serving
    # resilience") ---
    # a READY replica whose dispatch has been on-device longer than this
    # is declared hung: the supervisor fails it, requeues its in-flight
    # requests and re-warms it; 0 disables the watchdog
    hang_watchdog_s: float = 10.0
    # per-class retry budget for transient replica failures: a request
    # requeued off a failed replica is retried at most this many times
    # before resolving as ReplicaError (503); classes absent from the
    # map get no retries — streams continuations are never retried
    retry_budget: Dict[str, int] = field(
        default_factory=lambda: {"interactive": 1, "batch": 2}
    )
    # circuit-breaker re-warm backoff: first re-warm after this many
    # seconds, doubling per consecutive failure, capped at the max
    rewarm_backoff_s: float = 0.5
    rewarm_backoff_max_s: float = 30.0
    # grace added on top of the class deadline budget when the HTTP
    # layer bounds future.result(timeout=...) — the deadline is enforced
    # in the router; the grace covers result readback + response writing
    deadline_grace_ms: float = 500.0
    # ceiling for per-request deadline overrides: a request may carry its
    # own deadline_ms (a long-form chapter group's budget scales with its
    # chunk count instead of inheriting the flat class budget); the
    # router clamps any override into (0, max_deadline_ms] so a client
    # cannot park an entry in the EDF heap forever. 0.0 (the default)
    # derives max(120000.0, largest class deadline); an explicit value
    # must be >= every class deadline
    max_deadline_ms: float = 0.0

    def __post_init__(self):
        if self.replicas < 1:
            raise ValueError(f"fleet.replicas must be >= 1, got {self.replicas}")
        if self.queue_depth <= 0:
            raise ValueError(
                f"fleet.queue_depth must be > 0, got {self.queue_depth}"
            )
        if not (0.0 < self.shed_low_watermark <= self.shed_high_watermark <= 1.0):
            raise ValueError(
                "fleet watermarks must satisfy 0 < low <= high <= 1, got "
                f"low={self.shed_low_watermark} high={self.shed_high_watermark}"
            )
        if not self.class_deadline_ms:
            raise ValueError("fleet.class_deadline_ms must be non-empty")
        for name, ms in self.class_deadline_ms.items():
            if ms <= 0:
                raise ValueError(
                    f"fleet.class_deadline_ms[{name!r}] must be > 0, got {ms}"
                )
        if self.default_class not in self.class_deadline_ms:
            raise ValueError(
                f"fleet.default_class {self.default_class!r} is not a key of "
                f"class_deadline_ms {sorted(self.class_deadline_ms)}"
            )
        if self.stream_window <= 0:
            raise ValueError(
                f"fleet.stream_window must be > 0, got {self.stream_window}"
            )
        if self.stream_overlap < 0:
            raise ValueError(
                f"fleet.stream_overlap must be >= 0, got {self.stream_overlap}"
            )
        if self.stream_depth < 1:
            raise ValueError(
                f"fleet.stream_depth must be >= 1, got {self.stream_depth}"
            )
        if self.drain_timeout_s < 0:
            raise ValueError(
                f"fleet.drain_timeout_s must be >= 0, got {self.drain_timeout_s}"
            )
        if self.hang_watchdog_s < 0:
            raise ValueError(
                f"fleet.hang_watchdog_s must be >= 0 (0 disables), got "
                f"{self.hang_watchdog_s}"
            )
        for name, n in self.retry_budget.items():
            if n < 0:
                raise ValueError(
                    f"fleet.retry_budget[{name!r}] must be >= 0, got {n}"
                )
        if self.rewarm_backoff_s <= 0:
            raise ValueError(
                f"fleet.rewarm_backoff_s must be > 0, got {self.rewarm_backoff_s}"
            )
        if self.rewarm_backoff_max_s < self.rewarm_backoff_s:
            raise ValueError(
                "fleet.rewarm_backoff_max_s must be >= rewarm_backoff_s, got "
                f"{self.rewarm_backoff_max_s} < {self.rewarm_backoff_s}"
            )
        if self.deadline_grace_ms < 0:
            raise ValueError(
                f"fleet.deadline_grace_ms must be >= 0, got "
                f"{self.deadline_grace_ms}"
            )
        if self.max_deadline_ms < 0:
            raise ValueError(
                f"fleet.max_deadline_ms must be >= 0 (0 = derive), got "
                f"{self.max_deadline_ms}"
            )
        if self.max_deadline_ms == 0.0:
            object.__setattr__(
                self, "max_deadline_ms",
                max(120000.0, max(self.class_deadline_ms.values())),
            )
        elif self.max_deadline_ms < max(self.class_deadline_ms.values()):
            raise ValueError(
                "fleet.max_deadline_ms must be >= every class deadline "
                f"(it is the override ceiling), got {self.max_deadline_ms} "
                f"< max of {self.class_deadline_ms}"
            )


@dataclass(frozen=True)
class AutoscaleConfig:
    """Closed-loop fleet autoscaler knobs (serving/autoscale.py —
    ARCHITECTURE.md "Autoscaling & traffic model").

    Disabled by default: with ``enabled: false`` nothing changes — the
    replica count stays wherever ``scale_to()`` last put it. Enabled, a
    policy thread watches the signals the router already exports
    (pending-heap depth vs the shed watermarks, shed/deadline-miss
    rates, per-replica dispatch occupancy) and drives ``scale_to()``
    inside ``[min_replicas, max_replicas]`` with hysteresis and
    cooldowns. The scale-up cost model is MEASURED, not assumed: the
    ``serve_replica_warmup_seconds`` histogram (sampled from actual
    replica warm-ups through the persistent compile cache) stretches
    both the post-scale-up cooldown and the calm window required before
    shedding capacity again.
    """

    enabled: bool = False
    min_replicas: int = 1
    max_replicas: int = 4
    # policy tick period; the loop is a stop-aware Event.wait, never a
    # bare time.sleep (jaxlint JL016), so drain/shutdown is not blocked
    interval_s: float = 0.25
    # -- scale-up triggers (any one fires) --
    # pending-heap depth as a fraction of fleet.queue_depth; sits below
    # shed_high_watermark on purpose — capacity should grow BEFORE the
    # router starts shedding
    up_queue_fraction: float = 0.5
    # instantaneous busy fraction of READY replicas; only fires with a
    # backlog at least one-deep per live replica (floor 2) SUSTAINED
    # for a full tick — a single mid-dispatch snapshot is not pressure
    up_occupancy: float = 0.9
    # shed + deadline-miss events per second over the last tick
    up_pressure_rate: float = 1.0
    # -- scale-down (all must hold, sustained) --
    down_queue_fraction: float = 0.05
    down_occupancy: float = 0.5
    # calm must persist this long (stretched by the measured warm-up
    # cost, see warmup_cost_factor) before one replica is drained
    down_stable_s: float = 5.0
    # -- hysteresis / bounds --
    cooldown_up_s: float = 2.0
    cooldown_down_s: float = 10.0
    # replicas added per scale-up decision at extreme pressure (depth
    # past twice the up watermark); ordinary pressure adds one
    max_step: int = 2
    # cost model: assumed warm-up seconds until the first measured
    # sample lands in serve_replica_warmup_seconds
    assumed_warmup_s: float = 10.0
    # the calm window before a scale-down is max(down_stable_s,
    # warmup_cost_factor * measured-warmup): capacity that was expensive
    # to warm is held longer against oscillating load
    warmup_cost_factor: float = 1.0

    def __post_init__(self):
        if self.min_replicas < 1:
            raise ValueError(
                f"autoscale.min_replicas must be >= 1, got {self.min_replicas}"
            )
        if self.max_replicas < self.min_replicas:
            raise ValueError(
                "autoscale.max_replicas must be >= min_replicas, got "
                f"{self.max_replicas} < {self.min_replicas}"
            )
        if self.interval_s <= 0:
            raise ValueError(
                f"autoscale.interval_s must be > 0, got {self.interval_s}"
            )
        if not (0.0 < self.up_queue_fraction <= 1.0):
            raise ValueError(
                "autoscale.up_queue_fraction must be in (0, 1], got "
                f"{self.up_queue_fraction}"
            )
        if not (0.0 <= self.down_queue_fraction < self.up_queue_fraction):
            raise ValueError(
                "autoscale.down_queue_fraction must satisfy 0 <= down < "
                f"up_queue_fraction, got {self.down_queue_fraction}"
            )
        if not (0.0 < self.up_occupancy <= 1.0):
            raise ValueError(
                "autoscale.up_occupancy must be in (0, 1], got "
                f"{self.up_occupancy}"
            )
        if not (0.0 <= self.down_occupancy < self.up_occupancy):
            raise ValueError(
                "autoscale.down_occupancy must satisfy 0 <= down < "
                f"up_occupancy, got {self.down_occupancy}"
            )
        if self.up_pressure_rate < 0:
            raise ValueError(
                "autoscale.up_pressure_rate must be >= 0, got "
                f"{self.up_pressure_rate}"
            )
        for name in ("down_stable_s", "cooldown_up_s", "cooldown_down_s",
                     "warmup_cost_factor"):
            if getattr(self, name) < 0:
                raise ValueError(
                    f"autoscale.{name} must be >= 0, got {getattr(self, name)}"
                )
        if self.max_step < 1:
            raise ValueError(
                f"autoscale.max_step must be >= 1, got {self.max_step}"
            )
        if self.assumed_warmup_s <= 0:
            raise ValueError(
                "autoscale.assumed_warmup_s must be > 0, got "
                f"{self.assumed_warmup_s}"
            )


@dataclass(frozen=True)
class StyleConfig:
    """Style-service knobs (serving/style.py — ARCHITECTURE.md "Style
    service").

    The reference encoder runs as its own AOT-precompiled subsystem over
    a ``(batch, ref_len)`` bucket lattice, fronted by a content-addressed
    LRU cache (sha256 of the reference bytes -> FiLM ``(gamma, beta)``
    vectors) so repeat styles never touch the encoder. Decoupling the
    reference length from the synthesis lattice's ``T_mel`` axis is the
    point: a long reference no longer inflates the output bucket.
    """

    # padded reference-mel lengths the style encoder compiles for (the
    # top bucket caps the longest admissible reference)
    ref_buckets: List[int] = field(default_factory=lambda: [256, 512, 1000])
    # encode batch sizes; empty = inherit serve.batch_buckets
    batch_buckets: List[int] = field(default_factory=list)
    # content-addressed LRU entries retained (gamma+beta vectors are a
    # few KB each; bounded by jaxlint JL012's no-unbounded-caches rule)
    cache_capacity: int = 512
    # allowlist directory for server-side "ref_audio" request paths; ""
    # (the default) refuses path-based references entirely — uploads go
    # through POST /styles instead
    ref_dir: str = ""

    def __post_init__(self):
        for name in ("ref_buckets", "batch_buckets"):
            vals = getattr(self, name)
            if name == "ref_buckets" and not vals:
                raise ValueError("serve.style.ref_buckets must be non-empty")
            if any(v <= 0 for v in vals):
                raise ValueError(
                    f"serve.style.{name} must be positive, got {vals}"
                )
            if sorted(vals) != list(vals) or len(set(vals)) != len(vals):
                raise ValueError(
                    f"serve.style.{name} must be strictly ascending, "
                    f"got {vals}"
                )
        if self.cache_capacity <= 0:
            raise ValueError(
                f"serve.style.cache_capacity must be > 0, "
                f"got {self.cache_capacity}"
            )


@dataclass(frozen=True)
class RolloutConfig:
    """Canary-gated rolling model rollout knobs (serving/lifecycle.py —
    ARCHITECTURE.md "Model lifecycle").

    A rollout verifies the new checkpoint's manifest, warms ONE canary
    replica on the new weights, replays a seeded golden set through the
    canary's AOT lattice (all-finite + mean-|Δmel| parity against the
    live version), and only then drain-replaces the remaining replicas
    one at a time. Any failure before commit aborts with the fleet
    untouched.
    """

    # gate POST /admin/rollout (and the RolloutManager wiring) — OFF by
    # default: a mutating admin surface must be opted into
    enabled: bool = False
    # golden-set size replayed through BOTH versions at the canary gate
    golden_set_size: int = 4
    # rng seed for the generated golden set (deterministic across runs)
    canary_seed: int = 0
    # mean |new_mel - old_mel| bound per golden request; generous by
    # default — the gate is against BROKEN weights (NaN, wrong tree,
    # garbage), not against intended retraining deltas
    canary_tolerance: float = 1e3
    # per-replica warm/drain wait during canary + roll phases
    replica_timeout_s: float = 600.0

    def __post_init__(self):
        if self.golden_set_size <= 0:
            raise ValueError(
                "serve.rollout.golden_set_size must be > 0, "
                f"got {self.golden_set_size}"
            )
        if self.canary_tolerance < 0:
            raise ValueError(
                "serve.rollout.canary_tolerance must be >= 0, "
                f"got {self.canary_tolerance}"
            )
        if self.replica_timeout_s <= 0:
            raise ValueError(
                "serve.rollout.replica_timeout_s must be > 0, "
                f"got {self.replica_timeout_s}"
            )


@dataclass(frozen=True)
class LongformConfig:
    """Long-form (chapter-length) synthesis knobs (serving/longform.py —
    ARCHITECTURE.md "Long-form synthesis").

    Two tiers behind ``POST /synthesize/longform``. **Chunked** (always
    available): the chapter is split at sentence boundaries into
    utterances that each fit the interactive lattice, synthesized as a
    deadline-sharing group of ``long_form``-class requests through the
    existing batcher/fleet, and stitched with prosodic continuity —
    per-chunk duration/pitch/energy controls carried across the seam
    plus an equal-power crossfade — streamed chunk-by-chunk (bounded
    memory, jaxlint JL019). **Ring** (``mesh_seq > 1``): one coherent
    chapter-length utterance compiled as a single ring-attention program
    over a ``seq``-axis mesh at the ``longform`` buckets below, with
    tier-b→tier-a degradation on ring failure decided at admission.
    """

    # seq-axis mesh size for the ring tier: devices the chapter-length
    # free-run shards its attention over (parallel/ring_attention.py);
    # 0 or 1 = chunked tier only (no ring programs compiled)
    mesh_seq: int = 0
    # padded text lengths the ring tier compiles for — the long-form
    # lattice ABOVE serve.src_buckets[-1]; every value must be divisible
    # by mesh_seq (ring shards the length axis evenly)
    src_buckets: List[int] = field(default_factory=lambda: [512, 1024])
    # padded mel lengths for the ring free-run output buffer (defaults
    # pair with src_buckets at serve.frames_per_phoneme=12); same
    # divisibility contract as src_buckets
    mel_buckets: List[int] = field(default_factory=lambda: [6144, 12288])
    # mel frames of equal-power crossfade at each chunk seam (chunked
    # tier); converted to wav samples via the vocoder hop
    crossfade_frames: int = 8
    # admission cap on chapter size (chunks after sentence packing)
    max_chunks: int = 64
    # chunked-tier in-flight bound: at most this many chunk requests are
    # submitted ahead of the stitch point, so resident memory is
    # O(group_depth) chunk wavs — never the whole chapter (jaxlint JL019
    # polices the concatenate-the-chapter failure mode)
    group_depth: int = 4
    # per-chunk share of the chapter group's deadline budget: the group
    # budget is n_chunks * this, clamped to fleet.max_deadline_ms
    deadline_ms_per_chunk: float = 2000.0
    # tier selection at admission: "auto" rings when the ring tier is up
    # and the chapter fits a ring bucket, else chunks; "chunked"/"ring"
    # force a tier ("ring" still degrades to chunked on failure)
    tier: str = "auto"

    def __post_init__(self):
        if self.mesh_seq < 0:
            raise ValueError(
                f"serve.longform.mesh_seq must be >= 0, got {self.mesh_seq}"
            )
        for name in ("src_buckets", "mel_buckets"):
            vals = getattr(self, name)
            if not vals:
                raise ValueError(f"serve.longform.{name} must be non-empty")
            if any(v <= 0 for v in vals):
                raise ValueError(
                    f"serve.longform.{name} must be positive, got {vals}"
                )
            if sorted(vals) != list(vals) or len(set(vals)) != len(vals):
                raise ValueError(
                    f"serve.longform.{name} must be strictly ascending, "
                    f"got {vals}"
                )
            if self.mesh_seq > 1 and any(v % self.mesh_seq for v in vals):
                raise ValueError(
                    f"serve.longform.{name} must be divisible by "
                    f"mesh_seq={self.mesh_seq} (ring shards the length "
                    f"axis evenly), got {vals}"
                )
        if self.crossfade_frames < 0:
            raise ValueError(
                f"serve.longform.crossfade_frames must be >= 0, "
                f"got {self.crossfade_frames}"
            )
        if self.max_chunks <= 0:
            raise ValueError(
                f"serve.longform.max_chunks must be > 0, got {self.max_chunks}"
            )
        if self.group_depth < 1:
            raise ValueError(
                f"serve.longform.group_depth must be >= 1, "
                f"got {self.group_depth}"
            )
        if self.deadline_ms_per_chunk <= 0:
            raise ValueError(
                f"serve.longform.deadline_ms_per_chunk must be > 0, "
                f"got {self.deadline_ms_per_chunk}"
            )
        if self.tier not in ("auto", "chunked", "ring"):
            raise ValueError(
                "serve.longform.tier must be 'auto'|'chunked'|'ring', "
                f"got {self.tier!r}"
            )


@dataclass(frozen=True)
class ClusterConfig:
    """Distributed control plane knobs (serving/cluster.py —
    ARCHITECTURE.md "Distributed control plane").

    Disabled by default: with ``enabled: false`` the fleet router keeps
    its in-process replica engines and nothing here applies. Enabled,
    every replica is a separate *process* (cli/replica.py) that owns a
    full AOT engine and registers with the router over HTTP; liveness is
    heartbeat leases, dispatch is hedged with per-class timeouts, and
    the autoscaler's scale_to() spawns/drains real processes.
    """

    enabled: bool = False
    # control-plane bind address for the router's /register + /heartbeat
    # endpoints (port 0 = ephemeral, the bound port is advertised to
    # spawned replicas via --router)
    control_host: str = "127.0.0.1"
    control_port: int = 0
    # replica -> router heartbeat cadence; a lease is granted for
    # heartbeat_interval_s * (lease_miss_budget + 1) and renewed on every
    # beat, so a replica may miss `lease_miss_budget` consecutive beats
    # before the lease expires and the router fails it
    heartbeat_interval_s: float = 0.5
    lease_miss_budget: int = 3
    # hedged dispatch: a second request goes to a different host once the
    # first has been outstanding longer than this quantile of the class's
    # observed wire latency (serve_wire_latency_seconds), clamped into
    # [hedge_min_ms, hedge_max_ms]; first response wins, the loser's
    # connection is torn down. 0 quantile disables hedging.
    hedge_quantile: float = 0.95
    hedge_min_ms: float = 50.0
    hedge_max_ms: float = 2000.0
    # TCP connect timeout for every control + dispatch connection; the
    # per-attempt read timeout derives from the request's class deadline
    # (never unbounded — jaxlint JL024 enforces this structurally)
    connect_timeout_s: float = 2.0
    # a spawned replica process must register within this budget or the
    # spawn is declared failed (covers engine AOT warmup; the measured
    # serve_replica_warmup_seconds histogram still feeds the autoscaler)
    spawn_grace_s: float = 120.0
    # /healthz readiness quorum: the server answers 503 until at least
    # this many replicas hold live leases and are READY
    quorum: int = 1
    # bounded per-replica idempotency cache (keys of executed dispatch
    # batches -> cached wire response), so a hedge or wire retry of an
    # already-executed batch never re-runs the lattice
    idempotency_cache: int = 256

    def __post_init__(self):
        if self.heartbeat_interval_s <= 0:
            raise ValueError(
                f"serve.cluster.heartbeat_interval_s must be > 0, "
                f"got {self.heartbeat_interval_s}"
            )
        if self.lease_miss_budget < 1:
            raise ValueError(
                f"serve.cluster.lease_miss_budget must be >= 1, "
                f"got {self.lease_miss_budget}"
            )
        if not (0.0 <= self.hedge_quantile < 1.0):
            raise ValueError(
                f"serve.cluster.hedge_quantile must be in [0, 1) "
                f"(0 disables hedging), got {self.hedge_quantile}"
            )
        if self.hedge_min_ms < 0:
            raise ValueError(
                f"serve.cluster.hedge_min_ms must be >= 0, "
                f"got {self.hedge_min_ms}"
            )
        if self.hedge_max_ms < self.hedge_min_ms:
            raise ValueError(
                "serve.cluster.hedge_max_ms must be >= hedge_min_ms, got "
                f"{self.hedge_max_ms} < {self.hedge_min_ms}"
            )
        if self.connect_timeout_s <= 0:
            raise ValueError(
                f"serve.cluster.connect_timeout_s must be > 0, "
                f"got {self.connect_timeout_s}"
            )
        if self.spawn_grace_s <= 0:
            raise ValueError(
                f"serve.cluster.spawn_grace_s must be > 0, "
                f"got {self.spawn_grace_s}"
            )
        if self.quorum < 1:
            raise ValueError(
                f"serve.cluster.quorum must be >= 1, got {self.quorum}"
            )
        if self.idempotency_cache < 1:
            raise ValueError(
                f"serve.cluster.idempotency_cache must be >= 1, "
                f"got {self.idempotency_cache}"
            )

    @property
    def lease_ttl_s(self) -> float:
        """Lease duration granted per heartbeat: the replica may miss
        ``lease_miss_budget`` consecutive beats before expiry."""
        return self.heartbeat_interval_s * (self.lease_miss_budget + 1)


@dataclass(frozen=True)
class TiersConfig:
    """Quality-tiered serving (serving/tiers.py): precision variants of
    the acoustic lattice plus an optional distilled student model,
    canary-gated against the teacher and routed by traffic class.

    A tier name is ``<model>-<precision>`` (``teacher-f32``,
    ``teacher-bf16``, ``student-int8``): the model half picks the param
    tree (teacher checkpoint vs the distilled student registered as a
    second model version), the precision half picks the lattice's
    precision axis. A tier only ships if its golden-set mel-L2 against
    the teacher-f32 engine holds under ``tier_tolerance``; a failed gate
    falls back to ``default_tier`` so routing never loses requests.
    """

    enabled: bool = False
    # precision tiers the lattice compiles (registry.PRECISIONS subset;
    # the first entry is the default precision for untagged requests)
    precisions: List[str] = field(default_factory=lambda: ["f32"])
    # traffic class -> tier name; classes absent here ride default_tier
    class_tier: Dict[str, str] = field(default_factory=dict)
    # the always-shipped reference tier (the quality anchor; its gate is
    # identity so it can never fail)
    default_tier: str = "teacher-f32"
    # golden-set mel-L2 ceiling vs the teacher-f32 engine for a tier to
    # ship (same spirit as rollout.canary_tolerance; loose default for
    # tiny CI configs — production presets tighten it)
    tier_tolerance: float = 1e3
    # golden probe set (reuses lifecycle.make_golden_set)
    golden_set_size: int = 4
    golden_seed: int = 0
    # the distilled student checkpoint (training/distill.py output);
    # empty = no student tiers available
    student_ckpt_path: str = ""

    def __post_init__(self):
        allowed = ("f32", "bf16", "int8")
        if not self.precisions:
            raise ValueError("serve.tiers.precisions must be non-empty")
        for p in self.precisions:
            if p not in allowed:
                raise ValueError(
                    f"serve.tiers.precisions entries must be in {allowed}, "
                    f"got {p!r}"
                )
        if len(set(self.precisions)) != len(self.precisions):
            raise ValueError(
                f"serve.tiers.precisions must be unique, got {self.precisions}"
            )
        names = [self.default_tier, *self.class_tier.values()]
        for name in names:
            model, sep, prec = name.partition("-")
            if not sep or model not in ("teacher", "student") \
                    or prec not in allowed:
                raise ValueError(
                    "tier names must be '<model>-<precision>' with model in "
                    f"(teacher, student) and precision in {allowed}, "
                    f"got {name!r}"
                )
        if self.tier_tolerance <= 0:
            raise ValueError(
                f"serve.tiers.tier_tolerance must be > 0, "
                f"got {self.tier_tolerance}"
            )
        if self.golden_set_size <= 0:
            raise ValueError(
                f"serve.tiers.golden_set_size must be > 0, "
                f"got {self.golden_set_size}"
            )


@dataclass(frozen=True)
class TraceConfig:
    """Distributed-tracing knobs (obs/trace.py — ARCHITECTURE.md
    "Fleet observability plane").

    Context propagation is always on (three strings riding each
    request); these knobs govern span *recording*: the bounded
    per-process ring served at ``GET /debug/spans``, and the tail
    sampler's healthy-traffic keep rate.  Every shed/504/hedge-won/
    deadline-miss trace is kept regardless of ``sample_rate`` — tail
    sampling only thins the healthy majority.
    """

    enabled: bool = True
    # bounded per-process finished-span ring (oldest evicted first)
    ring_capacity: int = 4096
    # bounded keep-store of pinned (tail-sampled) traces
    keep_traces: int = 256
    # deterministic keep probability for *healthy* traces; interesting
    # traces (error ladder, hedge winner, deadline miss) always keep
    sample_rate: float = 0.1

    def __post_init__(self):
        if self.ring_capacity < 1:
            raise ValueError(
                f"serve.trace.ring_capacity must be >= 1, "
                f"got {self.ring_capacity}"
            )
        if self.keep_traces < 1:
            raise ValueError(
                f"serve.trace.keep_traces must be >= 1, "
                f"got {self.keep_traces}"
            )
        if not (0.0 <= self.sample_rate <= 1.0):
            raise ValueError(
                f"serve.trace.sample_rate must be in [0, 1], "
                f"got {self.sample_rate}"
            )


@dataclass(frozen=True)
class SloConfig:
    """Multi-window burn-rate SLO accounting (obs/slo.py).

    Per traffic class, ``objectives`` states the availability target —
    the fraction of admitted requests that must resolve inside their
    deadline (neither shed after admission, nor 504ed, nor served past
    their SLO stamp). The engine differentiates the fleet's cumulative
    miss/shed/request counters into two sliding windows and publishes

        burn_rate = (bad / total) / (1 - objective)

    per (class, window) as ``serve_slo_burn_rate`` gauges: burn 1.0
    consumes the error budget exactly at sustainable rate. An alert
    (``slo_alert`` JSONL event) fires only when BOTH windows burn past
    their thresholds — the standard multi-window rule: the fast window
    catches the page-worthy spike, the slow window keeps one transient
    blip from paging.
    """

    enabled: bool = True
    # traffic class -> availability objective (fraction of requests that
    # must meet their deadline); classes absent here are not tracked
    objectives: Dict[str, float] = field(
        default_factory=lambda: {"interactive": 0.999, "batch": 0.99}
    )
    # sliding windows the cumulative counters are differentiated over
    fast_window_s: float = 60.0
    slow_window_s: float = 600.0
    # burn-rate thresholds per window (SRE handbook pairing: 14.4x burns
    # a 30-day budget in 2 days; 6x in 5 days)
    fast_burn_threshold: float = 14.4
    slow_burn_threshold: float = 6.0
    # evaluation cadence of the stop-aware policy loop
    tick_s: float = 5.0
    # traffic class -> audio-quality objective: the fraction of
    # validated wavs (obs/quality.py choke point) that must pass.
    # A separate stream from availability — the probe class exists
    # ONLY here (probe traffic is excluded from the latency SLO)
    quality_objectives: Dict[str, float] = field(
        default_factory=lambda: {
            "interactive": 0.99, "batch": 0.99, "probe": 0.99,
        }
    )

    def __post_init__(self):
        for klass, obj in self.objectives.items():
            if not (0.0 < obj < 1.0):
                raise ValueError(
                    f"serve.slo.objectives[{klass!r}] must be in (0, 1), "
                    f"got {obj}"
                )
        for klass, obj in self.quality_objectives.items():
            if not (0.0 < obj < 1.0):
                raise ValueError(
                    f"serve.slo.quality_objectives[{klass!r}] must be in "
                    f"(0, 1), got {obj}"
                )
        if self.fast_window_s <= 0:
            raise ValueError(
                f"serve.slo.fast_window_s must be > 0, "
                f"got {self.fast_window_s}"
            )
        if self.slow_window_s <= self.fast_window_s:
            raise ValueError(
                "serve.slo.slow_window_s must be > fast_window_s, got "
                f"{self.slow_window_s} <= {self.fast_window_s}"
            )
        if self.fast_burn_threshold <= 0 or self.slow_burn_threshold <= 0:
            raise ValueError(
                "serve.slo burn thresholds must be > 0, got "
                f"{self.fast_burn_threshold}/{self.slow_burn_threshold}"
            )
        if self.tick_s <= 0:
            raise ValueError(
                f"serve.slo.tick_s must be > 0, got {self.tick_s}"
            )


@dataclass(frozen=True)
class QualityConfig:
    """Audio-quality observability plane (obs/quality.py validators +
    serving/probes.py golden prober).

    Validator thresholds apply to every wav leaving the process
    (engine batch path, streaming windows, longform stitcher); probe
    knobs drive the background golden replays through the live fleet
    on their own traffic class — excluded from autoscaler pressure
    signals and the latency SLO, visible only to the quality SLO
    stream (``serve.slo.quality_objectives``).
    """

    enabled: bool = True
    # fraction of samples at >= 99.9% full scale before a wav fails
    clip_fraction_max: float = 0.5
    # longest exact-zero run (digital silence) a wav may carry
    silence_run_ms_max: float = 500.0
    # |mean| of the normalized wav (full scale = 1.0)
    dc_offset_max: float = 0.5
    # spectral flatness above this is a stuck/degenerate signal
    # (constant -> ~1.0; white noise -> ~0.56; speech far below)
    flatness_max: float = 0.9
    # skip the flatness check below this many samples (no spectrum)
    flatness_min_samples: int = 256
    # traffic class golden probes ride on; must not collide with
    # tenant classes — the fleet admits it with probe_deadline_ms and
    # keeps it out of shed/pressure/latency-SLO accounting
    probe_class: str = "probe"
    probe_deadline_ms: float = 30_000.0
    # cadence of the background prober's rounds
    probe_interval_s: float = 30.0
    # RMS mel-L2 drift vs the pinned anchor before the prober pages
    # (healthy drift is ~0: same lattice, same seeds, same weights)
    probe_mel_tolerance: float = 10.0
    # RMS FiLM (gamma, beta) drift vs the pinned style baseline
    probe_style_tolerance: float = 10.0
    # where pinned anchors live ("" = alongside train.path.log_path)
    anchor_dir: str = ""

    def __post_init__(self):
        for name in ("clip_fraction_max", "flatness_max"):
            v = getattr(self, name)
            if not (0.0 < v <= 1.0):
                raise ValueError(
                    f"serve.quality.{name} must be in (0, 1], got {v}"
                )
        for name in (
            "silence_run_ms_max", "dc_offset_max", "probe_deadline_ms",
            "probe_interval_s", "probe_mel_tolerance",
            "probe_style_tolerance",
        ):
            v = getattr(self, name)
            if v <= 0:
                raise ValueError(
                    f"serve.quality.{name} must be > 0, got {v}"
                )
        if self.flatness_min_samples < 2:
            raise ValueError(
                "serve.quality.flatness_min_samples must be >= 2, got "
                f"{self.flatness_min_samples}"
            )
        if not self.probe_class:
            raise ValueError("serve.quality.probe_class must be non-empty")


@dataclass(frozen=True)
class ServeConfig:
    """Continuous-batching synthesis server knobs (serving/engine.py,
    serving/batcher.py).

    The three bucket lists span the AOT-precompiled shape lattice: every
    served dispatch runs at some ``(batch, L_src, T_mel)`` drawn from
    their cross product, compiled once at server start. ``T_mel`` bounds
    the free-run output buffer (``max_mel_len``); the style-reference
    mel rides its own ``serve.style.ref_buckets`` axis (serving/style.py)
    so reference length never inflates the output bucket.
    """

    # batch sizes the engine compiles for; a dispatch of n requests runs
    # at the smallest bucket >= n
    batch_buckets: List[int] = field(default_factory=lambda: [1, 2, 4, 8])
    # padded text lengths (multiples of the dataset src bucket work well;
    # the top bucket caps the longest admissible utterance)
    src_buckets: List[int] = field(default_factory=lambda: [32, 64, 128, 256])
    # padded mel lengths: reference-mel input AND free-run output buffer
    mel_buckets: List[int] = field(default_factory=lambda: [256, 512, 1000])
    # admission deadline: a request is dispatched at most this long after
    # arrival (sooner when a full batch_buckets[-1] coalesces first)
    max_wait_ms: float = 10.0
    # bounded admission queue depth; submit blocks (stop-aware) when full
    queue_depth: int = 64
    # output-buffer sizing bound: a request with n phonemes needs
    # T_mel >= n * frames_per_phoneme (predictions past the buffer are
    # truncated, matching the reference's max_seq_len clamp)
    frames_per_phoneme: int = 12
    # donate request buffers into the compiled programs (XLA reuses the
    # padded input HBM for outputs; ignored with a warning on CPU)
    donate_buffers: bool = True
    # host->device transfer retry-with-backoff (DevicePrefetcher discipline)
    transfer_retries: int = 0
    transfer_backoff: float = 0.05
    host: str = "127.0.0.1"
    port: int = 8400
    # POST /debug/profile?seconds=N pulls a jax.profiler trace from the
    # live server (written under <log_path>/serve_profile); disable on
    # exposed deployments
    debug_profile: bool = True
    # emit serve_dispatch / http_request JSONL events (obs/events.py
    # schema) under train.path.log_path — req_id joins the two streams
    log_events: bool = False
    # host frontend worker pool: text normalization/G2P/phoneme encoding
    # runs off the dispatch path on this many threads, so frontend work
    # for request k+1 overlaps device dispatch of request k (requests
    # enter the queue with a resolved-or-pending frontend handle);
    # 0 = inline frontend on the HTTP handler thread (the pre-pipeline
    # behavior)
    frontend_workers: int = 2
    # fleet serving: multi-replica router, SLO admission, streaming
    fleet: FleetConfig = field(default_factory=FleetConfig)
    # distributed control plane: replica processes with heartbeat leases
    # and hedged dispatch (disabled by default — in-process replicas)
    cluster: ClusterConfig = field(default_factory=ClusterConfig)
    # closed-loop autoscaler over the fleet (disabled by default)
    autoscale: AutoscaleConfig = field(default_factory=AutoscaleConfig)
    # style service: AOT reference-encoder lattice + embedding cache
    style: StyleConfig = field(default_factory=StyleConfig)
    # canary-gated rolling model rollout (disabled by default)
    rollout: RolloutConfig = field(default_factory=RolloutConfig)
    # long-form (chapter-length) synthesis: chunk+stitch tier always on,
    # ring-attention tier when longform.mesh_seq > 1
    longform: LongformConfig = field(default_factory=LongformConfig)
    # quality tiers: precision lattice axis + distilled fast tier,
    # canary-gated and routed by class (disabled by default — one
    # teacher-f32 tier, byte-identical to the pre-tier engine)
    tiers: TiersConfig = field(default_factory=TiersConfig)
    # mesh geometry of ONE replica (parallel/mesh.py resolve_mesh — the
    # same resolution path as train.parallel): [1, 1] keeps the
    # single-device engine byte-for-byte; [dp, tp] makes every replica a
    # dp x tp mesh slice whose lattice programs compile with the batch
    # axis sharded over ``data`` (buckets divisible by dp) and outputs
    # replicated for host readback. Weights replicate unless
    # partition_rules opt into tensor parallelism — replicated weights
    # keep a mesh replica bit-identical to the 1x1 one from the same
    # checkpoint (the cross-mesh serving contract).
    parallel: ParallelConfig = field(default_factory=ParallelConfig)
    # distributed tracing: span ring sizing + tail-sampling keep rate
    trace: TraceConfig = field(default_factory=TraceConfig)
    # multi-window SLO burn-rate accounting per traffic class
    slo: SloConfig = field(default_factory=SloConfig)
    # audio-quality plane: output validators + live golden probes
    quality: QualityConfig = field(default_factory=QualityConfig)

    def __post_init__(self):
        for name in ("batch_buckets", "src_buckets", "mel_buckets"):
            vals = getattr(self, name)
            if not vals:
                raise ValueError(f"serve.{name} must be non-empty")
            if any(v <= 0 for v in vals):
                raise ValueError(f"serve.{name} must be positive, got {vals}")
            if sorted(vals) != list(vals) or len(set(vals)) != len(vals):
                raise ValueError(
                    f"serve.{name} must be strictly ascending, got {vals}"
                )
        if self.max_wait_ms < 0:
            raise ValueError(f"serve.max_wait_ms must be >= 0, got {self.max_wait_ms}")
        if self.queue_depth <= 0:
            raise ValueError(f"serve.queue_depth must be > 0, got {self.queue_depth}")
        if self.frames_per_phoneme <= 0:
            raise ValueError(
                f"serve.frames_per_phoneme must be > 0, got {self.frames_per_phoneme}"
            )
        if self.frontend_workers < 0:
            raise ValueError(
                f"serve.frontend_workers must be >= 0 (0 = inline), "
                f"got {self.frontend_workers}"
            )


@dataclass(frozen=True)
class Config:
    """The full (preprocess, model, train) triple, plus the serve block."""

    preprocess: PreprocessConfig = field(default_factory=PreprocessConfig)
    model: ModelConfig = field(default_factory=ModelConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    serve: ServeConfig = field(default_factory=ServeConfig)


def load_yaml(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return yaml.safe_load(f) or {}


def load_config(
    preprocess: Optional[str] = None,
    model: Optional[str] = None,
    train: Optional[str] = None,
    preset: Optional[str] = None,
) -> Config:
    """Load a Config from explicit YAML paths and/or a named preset."""
    if preset is not None:
        base = os.path.join(PRESET_DIR, preset)
        if not os.path.isdir(base):
            raise ValueError(
                f"Unknown preset {preset!r}; available: {sorted(os.listdir(PRESET_DIR))}"
            )
        preprocess = preprocess or os.path.join(base, "preprocess.yaml")
        model = model or os.path.join(base, "model.yaml")
        train = train or os.path.join(base, "train.yaml")
    pc = _build(PreprocessConfig, load_yaml(preprocess)) if preprocess else PreprocessConfig()
    mc = _build(ModelConfig, load_yaml(model)) if model else ModelConfig()
    # the serve.* block rides in train.yaml (a fourth file for a handful of
    # server knobs would be ceremony); absent -> defaults
    train_data = load_yaml(train) if train else {}
    serve_data = train_data.pop("serve", None) if isinstance(train_data, dict) else None
    tc = _build(TrainConfig, train_data) if train else TrainConfig()
    sc = _build(ServeConfig, serve_data, "serve") if serve_data else ServeConfig()
    return Config(preprocess=pc, model=mc, train=tc, serve=sc)


def load_stats(preprocessed_path: str) -> Dict[str, List[float]]:
    """stats.json: {"pitch": [min, max, mean, std], "energy": [...]}."""
    with open(os.path.join(preprocessed_path, "stats.json")) as f:
        return json.load(f)


def asdict(cfg) -> Dict[str, Any]:
    return dataclasses.asdict(cfg)
