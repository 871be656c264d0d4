"""Round-5 dropout micro-probe: mask generation + apply cost per impl at
the train step's heavy dropout shapes (PERF.md: 5.0 ms total measured as
the det->train delta; ~23 sites of [48,600,256] plus 3 of [48,600,1024]).

Times fwd+bwd of sum(dropout(x)^2) per impl, chained through a dummy
elementwise producer so the mask apply has something to fuse into.

Usage: python scripts/exp_dropout_r5.py
"""

import sys

from _bench_util import ITERS, require_tpu, timeit  # noqa: F401 (bootstraps sys.path/cache)

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_default_prng_impl", "rbg")

from speakingstyle_tpu.ops.dropout import DROPOUT_IMPLS, dropout

DT = jnp.bfloat16


def main():
    require_tpu()
    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)
    # N dependency-chained sites inside ONE jit: amplifies the per-site
    # cost well above the dispatch floor and matches the
    # real step's structure (~23 sites of [48,600,256], 3 of 1024ch)
    for shape, sites in (((48, 600, 256), 20), ((48, 600, 1024), 4)):
        x = jnp.asarray(rng.standard_normal(shape), DT)
        res = {}
        for impl in DROPOUT_IMPLS + ("none",):
            def loss(x_, k_, impl=impl):
                h = x_
                for i in range(sites):
                    h = h * 1.01 + 0.1  # producer for the mask to fuse into
                    if impl != "none":
                        h = dropout(
                            h, 0.2, jax.random.fold_in(k_, i), impl=impl
                        )
                return jnp.sum(h.astype(jnp.float32) ** 2)

            g = jax.jit(jax.grad(loss))
            res[impl] = timeit(g, x, key)
        base = res.pop("none")
        row = "  ".join(
            f"{k}={v:6.2f}ms ({(v - base) / sites * 1e3:+5.0f}us/site)"
            for k, v in res.items()
        )
        print(f"{shape} x{sites} sites: baseline={base:.2f}ms  {row}",
              flush=True)


if __name__ == "__main__":
    main()
