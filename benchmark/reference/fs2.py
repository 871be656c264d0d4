"""Plain reference of the acoustic model: FastSpeech2 with a style reference
encoder and FiLM, its loss, its gradients and its Adam step.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision: no
kernels, no buckets, no cache, no flax. It imports nothing of the program and
takes nothing the program made: weights come from ``init_params`` (numpy,
from the seed), and the program is *given* those same weights through a
checkpoint. The parameter tree uses the published module names, so one tree
serves both.

``quant`` is the control's hook: a function applied to both operands of every
matrix product and convolution (fp8 rounding for a bf16 configuration).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5
BN_EPS = 1e-5
BN_MOMENTUM = 0.9


# --------------------------------------------------------------------------
# hyper-parameters and weights
# --------------------------------------------------------------------------

def hyper(model: dict) -> dict:
    """The sizes the equations need, from a configuration file's ``model``."""
    tf, ref = model["transformer"], model["reference_encoder"]
    vp, ve = model["variance_predictor"], model["variance_embedding"]
    return {
        "vocab": model["vocab_size"], "n_mels": model["n_mel_channels"],
        "d": tf["encoder_hidden"], "enc_layers": tf["encoder_layer"],
        "dec_layers": tf["decoder_layer"], "enc_heads": tf["encoder_head"],
        "dec_heads": tf["decoder_head"], "ffn": tf["conv_filter_size"],
        "ffn_k": tuple(tf["conv_kernel_size"]),
        "enc_drop": tf["encoder_dropout"], "dec_drop": tf["decoder_dropout"],
        "ref_convs": ref["conv_layer"], "ref_ch": ref["conv_filter_size"],
        "ref_k": ref["conv_kernel_size"], "ref_layers": ref["encoder_layer"],
        "ref_heads": ref["encoder_head"], "ref_d": ref["encoder_hidden"],
        "ref_drop": ref["dropout"],
        "vp_ch": vp["filter_size"], "vp_k": vp["kernel_size"],
        "vp_drop": vp["dropout"], "n_bins": ve["n_bins"],
        "post_ch": model["postnet_embedding_dim"],
        "post_k": model["postnet_kernel_size"],
        "post_layers": model["postnet_layers"],
        "post_drop": model.get("postnet_dropout", 0.5),
        "n_speakers": model["n_speakers"] if model["multi_speaker"] else 0,
        "pitch_range": tuple(model["pitch_range"]),
        "energy_range": tuple(model["energy_range"]),
        "lambda_f": model.get("lambda_f", 0.0),
    }


def _shapes(hp: dict) -> dict:
    """path -> (shape, rule). Rules: ('normal', std) | 'ones' | 'zeros'."""
    out = {}
    d, ffn = hp["d"], hp["ffn"]

    def dense(path, cin, cout, bias=True, xavier=False):
        std = math.sqrt(2.0 / (cin + cout)) if xavier else 1.0 / math.sqrt(cin)
        out[path + "/kernel"] = ((cin, cout), ("normal", std))
        if bias:
            out[path + "/bias"] = ((cout,), "zeros")

    def conv(path, k, cin, cout):
        out[path + "/kernel"] = ((k, cin, cout), ("normal", 1.0 / math.sqrt(k * cin)))
        out[path + "/bias"] = ((cout,), "zeros")

    def norm(path, c):
        out[path + "/scale"] = ((c,), "ones")
        out[path + "/bias"] = ((c,), "zeros")

    def fft(path, dm, inner, ks, film):
        for w in ("w_qs", "w_ks", "w_vs", "fc"):
            dense(f"{path}/slf_attn/{w}", dm, dm)
        norm(f"{path}/slf_attn/layer_norm", dm)
        conv(f"{path}/pos_ffn/w_1", ks[0], dm, inner)
        conv(f"{path}/pos_ffn/w_2", ks[1], inner, dm)
        norm(f"{path}/pos_ffn/layer_norm", dm)
        if film:
            out[f"{path}/film/s_gamma"] = ((1,), "ones")
            out[f"{path}/film/s_beta"] = ((1,), "ones")

    out["encoder/src_word_emb/embedding"] = (
        (hp["vocab"], d), ("normal", 1.0 / math.sqrt(d)))
    for i in range(hp["enc_layers"]):
        fft(f"encoder/layer_stack/layer_{i}", d, ffn, hp["ffn_k"], True)
    for i in range(hp["dec_layers"]):
        fft(f"decoder/layer_stack/layer_{i}", d, ffn, hp["ffn_k"], True)
    if hp["n_speakers"]:
        out["speaker_emb/embedding"] = (
            (hp["n_speakers"], d), ("normal", 1.0 / math.sqrt(d)))
    cin = hp["n_mels"]
    for i in range(hp["ref_convs"]):
        conv(f"reference_encoder/conv_{i}/conv", hp["ref_k"], cin, hp["ref_ch"])
        norm(f"reference_encoder/ln_{i}", hp["ref_ch"])
        cin = hp["ref_ch"]
    dense("reference_encoder/fftb_linear/linear", hp["ref_ch"], hp["ref_d"],
          bias=False, xavier=True)
    for i in range(hp["ref_layers"]):
        fft(f"reference_encoder/fftb_{i}", hp["ref_d"], hp["ref_ch"],
            (hp["ref_k"], hp["ref_k"]), False)
    dense("reference_encoder/feature_wise_affine/linear", hp["ref_d"],
          2 * hp["ref_d"], bias=False, xavier=True)
    for name in ("duration", "pitch", "energy"):
        p = f"variance_adaptor/{name}_predictor"
        conv(f"{p}/conv1d_1", hp["vp_k"], d, hp["vp_ch"])
        norm(f"{p}/layer_norm_1", hp["vp_ch"])
        conv(f"{p}/conv1d_2", hp["vp_k"], hp["vp_ch"], hp["vp_ch"])
        norm(f"{p}/layer_norm_2", hp["vp_ch"])
        dense(f"{p}/linear_layer", hp["vp_ch"], 1)
    out["variance_adaptor/duration_predictor/film/s_gamma"] = ((1,), "ones")
    out["variance_adaptor/duration_predictor/film/s_beta"] = ((1,), "ones")
    for name in ("pitch", "energy"):
        out[f"variance_adaptor/{name}_embedding/embedding"] = (
            (hp["n_bins"], d), ("normal", 1.0 / math.sqrt(d)))
    dense("mel_linear", d, hp["n_mels"])
    cin = hp["n_mels"]
    for i in range(hp["post_layers"]):
        cout = hp["n_mels"] if i == hp["post_layers"] - 1 else hp["post_ch"]
        conv(f"postnet/conv_{i}", hp["post_k"], cin, cout)
        norm(f"postnet/bn_{i}", cout)
        cin = cout
    return out


def draw_tree(shapes: dict, seed: int) -> dict:
    """One numpy draw for the whole tree, cut into leaves in sorted order.
    Identical on every backend and in every process for a given seed."""
    rng = np.random.default_rng([int(seed), 0x5EED])
    names = sorted(shapes)
    total = sum(int(np.prod(shapes[n][0])) for n in names
                if isinstance(shapes[n][1], tuple))
    noise = rng.standard_normal(total, dtype=np.float32)
    tree, at = {}, 0
    for n in names:
        shape, rule = shapes[n]
        if rule == "ones":
            leaf = np.ones(shape, np.float32)
        elif rule == "zeros":
            leaf = np.zeros(shape, np.float32)
        else:
            size = int(np.prod(shape))
            leaf = (noise[at:at + size] * np.float32(rule[1])).reshape(shape)
            at += size
        node = tree
        parts = n.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = leaf
    return tree


def init_params(hp: dict, seed: int) -> dict:
    return draw_tree(_shapes(hp), seed)


def init_batch_stats(hp: dict) -> dict:
    out = {}
    for i in range(hp["post_layers"]):
        c = hp["n_mels"] if i == hp["post_layers"] - 1 else hp["post_ch"]
        out[f"bn_{i}"] = {"mean": np.zeros((c,), np.float32),
                          "var": np.ones((c,), np.float32)}
    return {"postnet": out}


def flatten(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        path = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(flatten(v, path))
        else:
            out[path] = v
    return out


# --------------------------------------------------------------------------
# layers
# --------------------------------------------------------------------------

def _ident(x):
    return x


class Ctx:
    """Dropout key stream and the control's rounding hook."""

    def __init__(self, key=None, quant=None):
        self.key = key
        self.n = 0
        self.q = quant or _ident

    def dropout(self, x, rate):
        if self.key is None or rate == 0.0:
            return x
        self.n += 1
        keep = jax.random.bernoulli(
            jax.random.fold_in(self.key, self.n), 1.0 - rate, x.shape)
        return jnp.where(keep, x / (1.0 - rate), 0.0)


def dense(c, p, x):
    y = jnp.matmul(c.q(x), c.q(p["kernel"]), precision=HIGHEST)
    return y + p["bias"] if "bias" in p else y


def conv1d(c, p, x):
    y = jax.lax.conv_general_dilated(
        c.q(x), c.q(p["kernel"]), (1,), "SAME",
        dimension_numbers=("NWC", "WIO", "NWC"), precision=HIGHEST)
    return y + p["bias"]


def layer_norm(p, x):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


def position_table(n, d):
    pos = np.arange(n, dtype=np.float64)[:, None]
    i = np.arange(d, dtype=np.float64)[None, :]
    angle = pos / np.power(10000.0, 2.0 * np.floor(i / 2.0) / d)
    table = np.where(np.arange(d)[None, :] % 2 == 0, np.sin(angle), np.cos(angle))
    return jnp.asarray(table, jnp.float32)


def attention(c, p, x, pad, heads, drop):
    b, length, d = x.shape
    dh = d // heads
    q = dense(c, p["w_qs"], x).reshape(b, length, heads, dh)
    k = dense(c, p["w_ks"], x).reshape(b, length, heads, dh)
    v = dense(c, p["w_vs"], x).reshape(b, length, heads, dh)
    s = jnp.einsum("bqhd,bkhd->bhqk", c.q(q), c.q(k), precision=HIGHEST)
    s = s / math.sqrt(dh)
    s = jnp.where(pad[:, None, None, :], -1e30, s)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", c.q(a), c.q(v), precision=HIGHEST)
    o = dense(c, p["fc"], o.reshape(b, length, d))
    o = c.dropout(o, drop)
    return layer_norm(p["layer_norm"], o + x)


def fft_block(c, p, x, pad, heads, drop, gamma=None, beta=None, exists=None):
    keep = ~pad[..., None]
    x = attention(c, p["slf_attn"], x, pad, heads, drop) * keep
    f = p["pos_ffn"]
    h = jax.nn.relu(conv1d(c, f["w_1"], x))
    if exists is not None:  # see ``front``: past the batch's own length
        h = h * exists
    h = conv1d(c, f["w_2"], h)
    h = c.dropout(h, drop)
    x = layer_norm(f["layer_norm"], h + x)
    if gamma is not None and "film" in p:
        x = (p["film"]["s_gamma"] * gamma + 1.0) * x + p["film"]["s_beta"] * beta
    return x * keep


def reference_encoder(c, hp, p, mel, pad, pool_len=None, exists=None):
    """mel [B,T,n_mels], pad [B,T] True at padding -> gamma, beta [B,1,d].
    The mean pool divides by the padded length, as published: ``pool_len``
    where the buffer is longer than the batch's own padded length."""
    x = mel * ~pad[..., None]
    for i in range(hp["ref_convs"]):
        x = jax.nn.relu(conv1d(c, p[f"conv_{i}"]["conv"], x))
        x = layer_norm(p[f"ln_{i}"], x)
        x = c.dropout(x, hp["ref_drop"])
        if exists is not None:  # past the batch's own padded length: nothing
            x = x * exists
    x = x * ~pad[..., None]
    x = x + position_table(x.shape[1], x.shape[2])[None]
    x = dense(c, p["fftb_linear"]["linear"], x)
    x = scan_blocks(c, [p[f"fftb_{i}"] for i in range(hp["ref_layers"])], x, pad,
                    hp["ref_heads"], hp["ref_drop"], exists=exists)
    pooled = x.sum(axis=1, keepdims=True) / (
        x.shape[1] if pool_len is None else pool_len)
    affine = dense(c, p["feature_wise_affine"]["linear"], pooled)
    return jnp.split(affine, 2, axis=-1)


def variance_predictor(c, hp, p, x, pad, gamma=None, beta=None, exists=None):
    for i in (1, 2):
        x = jax.nn.relu(conv1d(c, p[f"conv1d_{i}"], x))
        x = layer_norm(p[f"layer_norm_{i}"], x)
        x = c.dropout(x, hp["vp_drop"])
        if exists is not None:
            x = x * exists
    if gamma is not None:
        x = (p["film"]["s_gamma"] * gamma + 1.0) * x + p["film"]["s_beta"] * beta
    out = dense(c, p["linear_layer"], x)[..., 0]
    return jnp.where(pad, 0.0, out)


def bins(hp, which):
    lo, hi = hp[f"{which}_range"]
    return jnp.asarray(np.linspace(lo, hi, hp["n_bins"] - 1, dtype=np.float32))


def scan_blocks(c, layers, x, pad, heads, drop, gamma=None, beta=None,
                exists=None):
    """Identical blocks one after another, as a scan over their stacked
    parameters: the same arithmetic, one compiled body instead of N."""
    stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *layers)
    c.n += 1
    base = c.key if c.key is not None else jax.random.PRNGKey(0)
    keys = jax.random.split(jax.random.fold_in(base, c.n), len(layers))

    def body(x, layer):
        lp, k = layer
        sub = Ctx(k if c.key is not None else None, c.q)
        return fft_block(sub, lp, x, pad, heads, drop, gamma, beta, exists), None

    return jax.lax.scan(body, x, (stacked, keys))[0]


def stack(c, hp, p, x, pad, heads, drop, n, gamma, beta, exists=None):
    x = x + position_table(x.shape[1], x.shape[2])[None]
    return scan_blocks(c, [p["layer_stack"][f"layer_{i}"] for i in range(n)],
                       x, pad, heads, drop, gamma, beta, exists)


def length_regulate(x, durations, t_out):
    ends = jnp.cumsum(durations, axis=1)
    frame = jnp.arange(t_out)
    owner = jax.vmap(lambda e: jnp.searchsorted(e, frame, side="right"))(ends)
    owner = jnp.minimum(owner, x.shape[1] - 1)
    frames = jnp.take_along_axis(x, owner[..., None], axis=1)
    mel_lens = jnp.minimum(ends[:, -1], t_out)
    pad = frame[None, :] >= mel_lens[:, None]
    return frames * ~pad[..., None], mel_lens, pad


def postnet(c, hp, p, stats, x, train, keep=None):
    """Returns (residual, new batch statistics). Each layer is rematerialised
    in the backward pass so the full batch fits beside the program's peak.
    ``keep`` [B,T,1] zeroes frames past an utterance's end after every layer:
    on a padded buffer that is the zero padding an exact-length buffer has."""
    new_stats = {}
    n = hp["post_layers"]
    for i in range(n):
        def layer(x, pc, pb, i=i):
            y = conv1d(c, pc, x)
            if train:
                mu = y.mean((0, 1))
                var = jnp.square(y - mu).mean((0, 1))
            else:
                mu, var = stats[f"bn_{i}"]["mean"], stats[f"bn_{i}"]["var"]
            y = (y - mu) / jnp.sqrt(var + BN_EPS) * pb["scale"] + pb["bias"]
            if i < n - 1:
                y = jnp.tanh(y)
            return y, (mu, var)
        fn = jax.checkpoint(layer) if train else layer
        x, (mu, var) = fn(x, p[f"conv_{i}"], p[f"bn_{i}"])
        x = c.dropout(x, hp["post_drop"])
        if keep is not None:
            x = x * keep
        if train:
            old = stats[f"bn_{i}"]
            new_stats[f"bn_{i}"] = {
                "mean": BN_MOMENTUM * old["mean"] + (1 - BN_MOMENTUM) * mu,
                "var": BN_MOMENTUM * old["var"] + (1 - BN_MOMENTUM) * var}
    return x, new_stats


# --------------------------------------------------------------------------
# training: teacher-forced forward, loss, gradients in blocks of rows, Adam
# --------------------------------------------------------------------------

def front(c, hp, params, a, pool_len=None, bucket=None):
    """Everything before the postnet, teacher-forced, for a block of rows.
    ``bucket`` = (L, T) is the batch's own padded size where the buffers are
    longer: what lies past it is held at zero wherever a convolution could
    read it, because in the batch's own buffers it does not exist."""
    t_src, t_mel = a["texts"].shape[1], a["mels"].shape[1]
    in_l = in_t = None
    if bucket is not None:
        in_l = (jnp.arange(t_src) < bucket[0])[None, :, None]
        in_t = (jnp.arange(t_mel) < bucket[1])[None, :, None]
    inside = lambda x: x if in_l is None else x * in_l
    src_pad = jnp.arange(t_src)[None] >= a["src_lens"][:, None]
    mel_pad = jnp.arange(t_mel)[None] >= a["mel_lens"][:, None]
    gamma, beta = reference_encoder(
        c, hp, params["reference_encoder"], a["mels"], mel_pad, pool_len, in_t)
    x = params["encoder"]["src_word_emb"]["embedding"][a["texts"]]
    x = stack(c, hp, params["encoder"], x, src_pad, hp["enc_heads"],
              hp["enc_drop"], hp["enc_layers"], gamma, beta, in_l)
    if hp["n_speakers"]:
        x = inside(x + params["speaker_emb"]["embedding"][a["speakers"]][:, None, :])
    va = params["variance_adaptor"]
    log_d = variance_predictor(c, hp, va["duration_predictor"], x, src_pad,
                               gamma, beta, in_l)
    p_pred = variance_predictor(c, hp, va["pitch_predictor"], x, src_pad,
                                exists=in_l)
    x = inside(x + va["pitch_embedding"]["embedding"][
        jnp.searchsorted(bins(hp, "pitch"), a["pitches"], side="left")])
    e_pred = variance_predictor(c, hp, va["energy_predictor"], x, src_pad,
                                exists=in_l)
    x = inside(x + va["energy_embedding"]["embedding"][
        jnp.searchsorted(bins(hp, "energy"), a["energies"], side="left")])
    x, _, dec_pad = length_regulate(x, a["durations"], t_mel)
    x = stack(c, hp, params["decoder"], x, dec_pad, hp["dec_heads"],
              hp["dec_drop"], hp["dec_layers"], gamma, beta, in_t)
    mel = dense(c, params["mel_linear"], x)
    return mel, log_d, p_pred, e_pred, src_pad, dec_pad


def _film_l2(params):
    return sum(jnp.sum(jnp.square(v)) for k, v in flatten(params).items()
               if k.endswith("s_gamma") or k.endswith("s_beta"))


def _rows(a, lo, hi):
    return {k: v[lo:hi] for k, v in a.items()}


_FNS = {}


def _train_fns(hp, quant):
    """The three jitted pieces of one step, built once per (hp, quant)."""
    key = (id(hp), quant)
    if key in _FNS:
        return _FNS[key]

    @jax.jit
    def block(params, blk, k, cot, n_src, bucket):
        """One block of rows: its mel before the postnet, its share of the
        small losses, and the gradient of (mel . cot + small losses). Called
        twice per block, first with a zero cotangent for the mel alone: one
        compiled program instead of two."""
        def f(params):
            mel, log_d, p_pred, e_pred, src_pad, _ = front(
                Ctx(k, quant), hp, params, blk, bucket[1].astype(jnp.float32),
                bucket)
            keep = ~src_pad
            log_t = jnp.log(blk["durations"].astype(jnp.float32) + 1.0)
            d = jnp.sum(jnp.square(log_d - log_t) * keep) / n_src
            p = jnp.sum(jnp.square(p_pred - blk["pitches"]) * keep) / n_src
            e = jnp.sum(jnp.square(e_pred - blk["energies"]) * keep) / n_src
            return jnp.sum(mel * cot) + d + p + e, (mel, jnp.stack([d, p, e]))
        (_, (mel, parts)), g = jax.value_and_grad(f, has_aux=True)(params)
        return mel, parts, g

    @jax.jit
    def tail(post, stats_post, mel_out, mels, mel_keep, n_mel, k):
        def f(post, mel_out):
            res, new_stats = postnet(
                Ctx(k, quant), hp, post, stats_post, mel_out, True)
            keep = mel_keep[..., None]
            l_mel = jnp.sum(jnp.abs(mel_out - mels) * keep) / n_mel
            l_post = jnp.sum(jnp.abs(mel_out + res - mels) * keep) / n_mel
            return l_mel + l_post, (l_mel, l_post, new_stats)
        (_, aux), (g_post, g_mel) = jax.value_and_grad(
            f, argnums=(0, 1), has_aux=True)(post, mel_out)
        return aux, g_post, g_mel

    _FNS[key] = (block, tail)
    return _FNS[key]


def _pad_to(a, l_to, t_to):
    """A batch laid into longer buffers. Padded positions are masked or zero
    everywhere the equations look, so a real position reads what it read."""
    def grow(x, axis, to):
        width = [(0, 0)] * x.ndim
        width[axis] = (0, to - x.shape[axis])
        return jnp.pad(x, width)
    out = dict(a)
    for k in ("texts", "pitches", "energies", "durations"):
        out[k] = grow(a[k], 1, l_to)
    out["mels"] = grow(a["mels"], 1, t_to)
    return out


def loss_and_grads(hp, params, stats, batch, key, block_rows=8, quant=None,
                   clock=None, pad_to=None):
    """Loss and gradients of one batch: the front in blocks of rows, the
    postnet (batch statistics) over the whole batch, then the front's
    backward pass block by block against the postnet's cotangent."""
    block, tail = _train_fns(hp, quant)
    a = {k: jnp.asarray(v) for k, v in batch.items()}
    n, t_mel = a["mels"].shape[:2]
    n_src = jnp.maximum(a["src_lens"].sum(), 1).astype(jnp.float32)
    mel_keep = (jnp.arange(t_mel)[None] < jnp.minimum(
        a["durations"].sum(1), t_mel)[:, None])
    n_mel = jnp.maximum(mel_keep.sum(), 1).astype(jnp.float32) * hp["n_mels"]
    blocks = [(lo, min(lo + block_rows, n)) for lo in range(0, n, block_rows)]
    keys = [jax.random.fold_in(key, i) for i in range(len(blocks) + 1)]
    # the front runs on buffers of one size for every batch (one compiled
    # program), the postnet on the batch's own padded length (its batch
    # statistics count every position of that length)
    l_to, t_to = pad_to or (a["texts"].shape[1], t_mel)
    wide = _pad_to(a, l_to, t_to)
    pool_len = (jnp.asarray(a["texts"].shape[1], jnp.int32),
                jnp.asarray(t_mel, jnp.int32))  # the batch's own (L, T)
    tick = clock or (lambda name: None)
    tick("start")
    first = block(params, _rows(wide, *blocks[0]), keys[0],
                  jnp.zeros((blocks[0][1] - blocks[0][0], t_to, hp["n_mels"])),
                  n_src, pool_len)[0].block_until_ready()
    tick("first_block_call")
    del first
    mel_out = jnp.concatenate(
        [block(params, _rows(wide, lo, hi), keys[i],
               jnp.zeros((hi - lo, t_to, hp["n_mels"])), n_src, pool_len)[0]
         for i, (lo, hi) in enumerate(blocks)], axis=0)[:, :t_mel]
    mel_out.block_until_ready()
    tick("forward_blocks")
    (l_mel, l_post, new_stats), g_post, g_mel = tail(
        params["postnet"], stats["postnet"], mel_out, a["mels"], mel_keep,
        n_mel, keys[-1])
    g_mel.block_until_ready()
    tick("tail")
    grads, parts = None, jnp.zeros((3,))
    for i, (lo, hi) in enumerate(blocks):
        cot = jnp.pad(g_mel[lo:hi], ((0, 0), (0, t_to - t_mel), (0, 0)))
        _, p3, g = block(params, _rows(wide, lo, hi), keys[i], cot, n_src, pool_len)
        parts = parts + p3
        grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
    grads = {**grads, "postnet": jax.tree_util.tree_map(
        jnp.add, grads["postnet"], g_post)}
    jax.block_until_ready(grads)
    tick("backward_blocks")
    reg = _film_l2(params)
    if hp["lambda_f"]:
        g_reg = jax.grad(_film_l2)(params)
        grads = jax.tree_util.tree_map(
            lambda g, r: g + hp["lambda_f"] * r, grads, g_reg)
    total = l_mel + l_post + parts.sum() + hp["lambda_f"] * reg
    return float(total), grads, {"postnet": new_stats}


def adam_init(params):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"count": 0, "mu": zeros, "nu": zeros}


def learning_rate(opt: dict, count: int) -> float:
    current = count + 1.0
    if current > opt["ramp_steps"]:
        passed = sum(current > m for m in opt["anneal_steps"])
        return opt["anneal_lr"] * opt["anneal_rate"] ** passed
    return opt["init_lr"] + current / opt["ramp_steps"] * (
        opt["anneal_lr"] - opt["init_lr"])


def adam_step(opt: dict, params, grads, state):
    """clip by global norm -> Adam -> -lr. Returns (params, state, clipped)."""
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree_util.tree_leaves(grads)))
    scale = jnp.where(norm < opt["clip"], 1.0, opt["clip"] / norm)
    clipped = jax.tree_util.tree_map(lambda g: g * scale, grads)
    b1, b2 = opt["betas"]
    count = state["count"] + 1
    mu = jax.tree_util.tree_map(
        lambda m, g: b1 * m + (1 - b1) * g, state["mu"], clipped)
    nu = jax.tree_util.tree_map(
        lambda v, g: b2 * v + (1 - b2) * jnp.square(g), state["nu"], clipped)
    lr = learning_rate(opt, state["count"])
    c1, c2 = 1 - b1 ** count, 1 - b2 ** count
    new = jax.tree_util.tree_map(
        lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + opt["eps"]),
        params, mu, nu)
    return new, {"count": count, "mu": mu, "nu": nu}, clipped


def train_steps(hp, opt, params, stats, batches, seed, block_rows=8, quant=None,
                clock=None):
    """Follow the first ``len(batches)`` optimizer steps. Returns per-step
    losses, the first clipped gradient and the parameters after each step."""
    params = jax.tree_util.tree_map(jnp.asarray, params)
    stats = jax.tree_util.tree_map(jnp.asarray, stats)
    state = adam_init(params)
    key = jax.random.PRNGKey(int(seed) % (2 ** 31))
    pad_to = (max(b["texts"].shape[1] for b in batches),
              max(b["mels"].shape[1] for b in batches))
    losses, first_grad, after = [], None, []
    for i, batch in enumerate(batches):
        loss, grads, stats = loss_and_grads(
            hp, params, stats, batch, jax.random.fold_in(key, i),
            block_rows=block_rows, quant=quant, clock=clock, pad_to=pad_to)
        params, state, clipped = adam_step(opt, params, grads, state)
        if first_grad is None:
            first_grad = clipped
        losses.append(loss)
        after.append(params)
    return losses, first_grad, after
