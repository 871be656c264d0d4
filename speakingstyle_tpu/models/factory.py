"""Model factory: build the configured family's model (FastSpeech2 from
config + preprocessed-dataset stats; the decoder language model from its
config block alone).

Reference: utils/model.py:11-45 (get_model). Pitch/energy bin ranges come
from stats.json and the speaker count from speakers.json, both written by
the preprocessor.
"""

import json
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from speakingstyle_tpu.configs.config import Config
from speakingstyle_tpu.models.fastspeech2 import FastSpeech2


def load_dataset_stats(cfg: Config) -> Tuple[tuple, tuple, int]:
    """(pitch_min_max, energy_min_max, n_speakers) from the preprocessed dir."""
    root = cfg.preprocess.path.preprocessed_path
    pitch_stats, energy_stats, n_speakers = (-3.0, 12.0), (-2.0, 10.0), 1
    stats_path = os.path.join(root, "stats.json") if root else ""
    if stats_path and os.path.exists(stats_path):
        with open(stats_path) as f:
            stats = json.load(f)
        pitch_stats = tuple(stats["pitch"][:2])
        energy_stats = tuple(stats["energy"][:2])
    speakers_path = os.path.join(root, "speakers.json") if root else ""
    if speakers_path and os.path.exists(speakers_path):
        with open(speakers_path) as f:
            n_speakers = max(len(json.load(f)), 1)
    return pitch_stats, energy_stats, n_speakers


def reference_encoder_from_config(
    cfg: Config, n_position: Optional[int] = None, name: Optional[str] = None
):
    """The one place ReferenceEncoder kwargs are derived from config —
    shared by the model (fastspeech2.py) and the analyze CLI, so a
    constructor change can't silently diverge between them."""
    from speakingstyle_tpu.models.reference_encoder import ReferenceEncoder

    m = cfg.model
    ref = m.reference_encoder
    return ReferenceEncoder(
        n_conv_layers=ref.conv_layer,
        conv_filter_size=ref.conv_filter_size,
        conv_kernel_size=ref.conv_kernel_size,
        n_layers=ref.encoder_layer,
        n_head=ref.encoder_head,
        d_model=ref.encoder_hidden,
        dropout=ref.dropout,
        n_position=n_position or (m.max_seq_len + 1),
        conv_impl=m.conv_impl,
        dtype=jnp.dtype(m.compute_dtype),
        softmax_dtype=jnp.dtype(m.attention_softmax_dtype),
        attention_kernel=m.attention_kernel,
        dropout_impl=m.dropout_impl,
        **({"name": name} if name is not None else {}),
    )


def fft_stack_from_config(
    cfg: Config,
    which: str,  # "encoder" | "decoder"
    n_position: Optional[int] = None,
    seq_mesh=None,
    name: Optional[str] = None,
):
    """Encoder/Decoder construction from config (see
    reference_encoder_from_config for why this lives here)."""
    from speakingstyle_tpu.models.transformer import Decoder, Encoder

    m = cfg.model
    tf = m.transformer
    cls = {"encoder": Encoder, "decoder": Decoder}[which]
    return cls(
        n_layers=getattr(tf, f"{which}_layer"),
        d_model=getattr(tf, f"{which}_hidden"),
        n_head=getattr(tf, f"{which}_head"),
        d_inner=tf.conv_filter_size,
        kernel_sizes=tuple(tf.conv_kernel_size),
        dropout=getattr(tf, f"{which}_dropout"),
        n_position=n_position or (m.max_seq_len + 1),
        remat=cfg.train.sharding.remat,
        conv_impl=m.conv_impl,
        dtype=jnp.dtype(m.compute_dtype),
        softmax_dtype=jnp.dtype(m.attention_softmax_dtype),
        attention_kernel=m.attention_kernel,
        seq_mesh=seq_mesh,
        dropout_impl=m.dropout_impl,
        **({"name": name} if name is not None else {}),
    )


def build_model(
    cfg: Config, n_position: Optional[int] = None, seq_mesh=None
):
    """The model of ``cfg.model.family``: FastSpeech2 (``acoustic``) or the
    decoder language model (``decoder_lm``, models/mellum.py). ``seq_mesh`` (a Mesh with a "seq" axis) is required when
    cfg.model.attention_impl == "ring"; build one with
    parallel.mesh.make_seq_mesh() for long-sequence inference."""
    if cfg.model.family == "decoder_lm":
        from speakingstyle_tpu.models.mellum import DecoderLM

        return DecoderLM(cfg.model.decoder_lm,
                         dtype=jnp.dtype(cfg.model.compute_dtype))
    if cfg.model.attention_impl == "ring" and seq_mesh is None:
        raise ValueError(
            'attention_impl="ring" needs a seq mesh: '
            "build_model(cfg, seq_mesh=make_seq_mesh())"
        )
    if cfg.model.attention_impl != "ring":
        seq_mesh = None
    pitch_stats, energy_stats, n_speakers = load_dataset_stats(cfg)
    return FastSpeech2(
        config=cfg,
        pitch_stats=pitch_stats,
        energy_stats=energy_stats,
        n_speakers=n_speakers,
        n_position=n_position,
        seq_mesh=seq_mesh,
    )


def init_variables(model: FastSpeech2, cfg: Config, rng: jax.Array):
    """Initialize params/batch_stats with a minimal teacher-forced dummy
    batch (decoder_lm: a row of a few ids; parameters only, and under jit,
    so that nothing but the initializers runs on the device)."""
    if cfg.model.family == "decoder_lm":
        from speakingstyle_tpu.models.mellum import dummy_inputs
        from speakingstyle_tpu.parallel.registry import jit_program

        return jit_program(model.init)(rng, **dummy_inputs(cfg.model.decoder_lm))
    n_mels = cfg.preprocess.preprocessing.mel.n_mel_channels
    B, L, T = 2, 8, 16
    dummy = dict(
        speakers=jnp.zeros((B,), jnp.int32),
        texts=jnp.ones((B, L), jnp.int32),
        src_lens=jnp.full((B,), L, jnp.int32),
        mels=jnp.zeros((B, T, n_mels), jnp.float32),
        mel_lens=jnp.full((B,), T, jnp.int32),
        max_mel_len=T,
        p_targets=jnp.zeros((B, L), jnp.float32),
        e_targets=jnp.zeros((B, L), jnp.float32),
        d_targets=jnp.full((B, L), T // L, jnp.int32),
    )
    rngs = {"params": rng, "dropout": rng}
    return model.init(rngs, deterministic=True, **dummy)


def count_params(params) -> int:
    """Total parameter count (reference: utils/model.py:48-51)."""
    return int(
        sum(np.prod(p.shape) for p in jax.tree_util.tree_leaves(params))
    )
