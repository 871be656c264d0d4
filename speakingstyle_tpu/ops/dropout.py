"""Dropout with selectable mask generation, tuned for TPU.

The reference applies standard inverted dropout everywhere (reference:
transformer/SubLayers.py:55-57, model/modules.py:383-384); the math here
is identical — ``where(keep_mask, x / keep_prob, 0)`` with
``P(keep) = 1 - rate`` — but mask *generation* is the knob. Most of the
train step's dropout cost is RNG-bit materialization traffic (read on an
earlier installation; no reading in PERF_LEDGER.jsonl, ROADMAP C2), so:

* ``"bernoulli"`` — ``jax.random.bernoulli`` (what ``nn.Dropout`` does):
  32 random bits per element, converted to f32 uniforms, compared.
* ``"bits16"`` — 16 raw random bits per element (one u32 generates two
  masks), integer threshold compare, no float conversion. Halves the RNG
  traffic; quantizes the keep probability to 1/65536 steps (≤8e-6
  absolute, vs f32 uniforms' own 2^-24 granularity — negligible).
* ``"hash"`` — zero RNG materialization: a murmur3-finalizer
  (fmix32) counter hash of the flat element index, salted per call from
  the PRNG key. Pure elementwise arithmetic on an iota — XLA fuses it
  into the consumer, so no random bits ever touch HBM. fmix32 has full
  avalanche (every input bit flips every output bit with p≈0.5), which
  is far more than dropout masks need; the keep probability quantizes to
  1/2^32. NOT a cryptographic stream and deliberately so.

All impls draw from the module's "dropout" RNG collection and differ only
in mask bits; tests/test_ops.py::test_dropout_impls checks keep-rate
statistics, scaling, and determinism per impl.
"""

import flax.linen as nn
import jax
import jax.numpy as jnp

DROPOUT_IMPLS = ("bernoulli", "bits16", "hash")


def _u32(v: int):
    return jnp.uint32(v & 0xFFFFFFFF)


def _fmix32(h):
    """murmur3 32-bit finalizer: 6 fused elementwise ops, full avalanche."""
    h = h ^ (h >> 16)
    h = h * _u32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * _u32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def keep_mask(rng, rate: float, shape, impl: str = "bernoulli"):
    """Boolean keep mask with P(True) = 1 - rate."""
    if impl not in DROPOUT_IMPLS:
        raise ValueError(
            f"dropout impl must be one of {DROPOUT_IMPLS}, got {impl!r}"
        )
    if rate >= 1.0:
        # drop everything, exactly: the bits16/hash thresholds clamp at
        # 0xFFFF/0xFFFFFFFF and would otherwise keep a ~2^-16/2^-32 sliver
        # of elements (which dropout() would then scale by 1/(1-rate) = inf)
        return jnp.zeros(shape, jnp.bool_)
    if impl == "bernoulli":
        return jax.random.bernoulli(rng, 1.0 - rate, shape)
    n = 1
    for d in shape:
        n *= d
    if impl == "bits16":
        n32 = (n + 1) // 2
        # the three rng consumers live in mutually exclusive impl branches
        # — exactly one draw happens per call
        bits32 = jax.random.bits(rng, (n32,), jnp.uint32)  # jaxlint: disable=JL006
        bits16 = jax.lax.bitcast_convert_type(bits32, jnp.uint16).reshape(-1)
        thresh = min(0xFFFF, int(round(rate * 65536)))
        return (bits16[:n] >= jnp.uint16(thresh)).reshape(shape)
    salt = jax.random.bits(rng, (), jnp.uint32)
    idx = jax.lax.iota(jnp.uint32, n)
    h = _fmix32((idx * _u32(0x9E3779B9)) ^ salt)
    thresh = min(0xFFFFFFFF, int(round(rate * 2**32)))
    return (h >= _u32(thresh)).reshape(shape)


def dropout(x, rate: float, rng, impl: str = "bernoulli"):
    """Inverted dropout: zero with probability ``rate``, scale survivors by
    1/(1-rate). Identical math to flax ``nn.Dropout``; only the mask bits'
    provenance differs by ``impl``."""
    if rate == 0.0:
        return x
    if rate >= 1.0:
        # nn.Dropout semantics: drop everything, exactly (keep_mask also
        # guards this case; returning here just skips the dead where())
        return jnp.zeros_like(x)
    mask = keep_mask(rng, rate, x.shape, impl)
    return jnp.where(mask, x / (1.0 - rate), jnp.zeros_like(x))


class Dropout(nn.Module):
    """Drop-in replacement for ``nn.Dropout`` with a selectable mask impl
    (``ModelConfig.dropout_impl``). Reads the same "dropout" RNG
    collection, so switching impls changes no call-site wiring."""

    rate: float
    impl: str = "bernoulli"

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        if deterministic or self.rate == 0.0:
            return x
        return dropout(x, self.rate, self.make_rng("dropout"), self.impl)
