"""EmbedElim benchmark: the paper's write-collapse on the framework's
sparse embedding-update path (Zipfian token stream), vs the OCC scatter."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.optim.sparse import embed_elim_update, embed_occ_update

from benchmarks.common import emit, timeit


def main(quick=False):
    rng = np.random.default_rng(0)
    v, d = 50_000, 512
    t = 8192 if quick else 65_536
    table = jnp.asarray(rng.standard_normal((v, d)), jnp.float32)
    ids = jnp.asarray(np.minimum(rng.zipf(1.3, t), v) - 1, jnp.int32)
    grads = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)

    elim = jax.jit(lambda tb, i, g: embed_elim_update(tb, i, g, 1e-2))
    occ = jax.jit(lambda tb, i, g: embed_occ_update(tb, i, g, 1e-2))

    out, stats = elim(table, ids, grads)
    jax.block_until_ready(out)
    jax.block_until_ready(occ(table, ids, grads))

    te = timeit(lambda: jax.block_until_ready(elim(table, ids, grads)[0]))
    to = timeit(lambda: jax.block_until_ready(occ(table, ids, grads)))
    emit(
        "embed_elim.elim", te * 1e6,
        f"rows_written={int(stats.writes_elim)};eliminated={int(stats.eliminated)}",
    )
    emit("embed_elim.occ", to * 1e6, f"rows_written={int(stats.writes_occ)}")
    emit(
        "embed_elim.reduction", 0.0,
        f"write_reduction={int(stats.writes_occ)/max(int(stats.writes_elim),1):.2f}x",
    )


if __name__ == "__main__":
    from repro.compile_cache import use_persistent_cache

    use_persistent_cache()
    main()
