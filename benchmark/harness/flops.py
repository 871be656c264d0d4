"""Operations the model needs, computed from shapes. Matrix products and
convolutions only (2 per multiply-add); a backward pass counts twice its
forward; recomputation counts nothing. Lengths are the real (unpadded) ones,
so padding is waste and shows as a lower share of the peak."""


def _fft(n, d, inner, k1, k2):
    proj = 4 * 2 * n * d * d
    attn = 4 * n * n * d  # scores and weighted sum over n keys
    ffn = 2 * n * k1 * d * inner + 2 * n * k2 * inner * d
    return proj + attn + ffn


def acoustic_forward(m: dict, n_src: int, n_mel: int, with_reference: bool) -> float:
    """One utterance of n_src phonemes and n_mel frames."""
    tf, vp = m["transformer"], m["variance_predictor"]
    d, inner = tf["encoder_hidden"], tf["conv_filter_size"]
    k1, k2 = tf["conv_kernel_size"]
    n_mels = m["n_mel_channels"]
    total = tf["encoder_layer"] * _fft(n_src, d, inner, k1, k2)
    total += tf["decoder_layer"] * _fft(n_mel, d, inner, k1, k2)
    f, k = vp["filter_size"], vp["kernel_size"]
    total += 3 * (2 * n_src * k * d * f + 2 * n_src * k * f * f + 2 * n_src * f)
    total += 2 * n_mel * d * n_mels
    pc, pk = m["postnet_embedding_dim"], m["postnet_kernel_size"]
    total += 2 * n_mel * pk * (n_mels * pc + (m["postnet_layers"] - 2) * pc * pc
                               + pc * n_mels)
    if with_reference:
        total += reference_forward(m, n_mel)
    return float(total)


def reference_forward(m: dict, n_mel: int) -> float:
    ref = m["reference_encoder"]
    c, k, d = ref["conv_filter_size"], ref["conv_kernel_size"], ref["encoder_hidden"]
    total = 2 * n_mel * k * (m["n_mel_channels"] * c + (ref["conv_layer"] - 1) * c * c)
    total += 2 * n_mel * c * d
    total += ref["encoder_layer"] * _fft(n_mel, d, c, k, k)
    return float(total + 2 * d * 2 * d)


def train_step_flops(m: dict, lengths) -> float:
    """Forward and backward of the utterances [(n_src, n_mel)]."""
    return 3.0 * sum(acoustic_forward(m, s, t, True) for s, t in lengths)


def mha_call(b, h, d, t, backward: bool):
    """(operations, bytes) one fused-attention call needs at its own shapes
    [B, H, D, T], bf16: two products forward; four backward (the kernel's
    recomputation of the scores is not counted)."""
    ops = (8 if backward else 4) * b * h * t * t * d
    arrays = 7 if backward else 4
    return float(ops), float(arrays * b * h * d * t * 2)
