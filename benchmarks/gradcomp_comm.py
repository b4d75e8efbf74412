"""WORp gradient compression: communication volume + update fidelity.

The paper's distributed-learning motivation quantified: bytes placed on the
DP all-reduce per step (sketch + pass-II exact values vs dense), and the
cosine similarity between the compressed and the true mean gradient --
with error feedback the residual re-enters later steps, so fidelity is
cumulative (we report both instantaneous and 5-step-EF cosine).

Each mode runs at wire codec ``none`` (raw fp32 payloads) and
``size_adaptive`` (``repro.distributed.codecs``): the ``bytes_wire=`` /
``bytes_ratio=`` columns report the encoded bytes each worker places on
the all-reduce per step and the reduction vs the raw payload, from the
compressor's static ``comm_bytes`` stat."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.optim import gradcomp


def run(verbose: bool = True):
    rows = []
    from repro.launch.mesh import make_mesh_auto
    mesh = make_mesh_auto((1,), ("data",))
    n = 1 << 18  # 262k-coordinate gradient
    rng = np.random.default_rng(0)
    for mode in ("onepass", "twopass"):
        for codec in ("none", "size_adaptive"):
            cc = gradcomp.CompressorConfig(k=1024, rows=7, width=4096,
                                           candidates=2048, p=1.0,
                                           mode=mode, codec=codec)

            def step(a):
                return gradcomp.compress_step(a, cc, ("data",))

            f = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=P(),
                                      out_specs=P(), check_vma=False))
            # heavy-tailed synthetic gradient
            g = (rng.standard_t(3, size=n) *
                 (1 + 50 * (rng.random(n) < 0.001))).astype(np.float32)
            err = jnp.zeros(n, jnp.float32)
            cosines = []
            t0 = time.perf_counter()
            for _ in range(5):
                a = jnp.asarray(g) + err
                sparse, err, stats = f(a)
                c = float(jnp.dot(sparse, jnp.asarray(g)) /
                          (jnp.linalg.norm(sparse) *
                           jnp.linalg.norm(jnp.asarray(g)) + 1e-9))
                cosines.append(c)
            us = (time.perf_counter() - t0) * 1e6 / 5
            ratio = (float(stats["comm_floats"])
                     / float(stats["dense_floats"]))
            wire = float(stats["comm_bytes"])
            bratio = float(stats["dense_bytes"]) / wire
            tag = "" if codec == "none" else f"_{codec}"
            rows.append((f"gradcomp_{mode}{tag}_n{n}", us,
                         f"comm_ratio={ratio:.4f} bytes_wire={wire:.0f} "
                         f"bytes_ratio={bratio:.2f} "
                         f"cos_step1={cosines[0]:.3f} "
                         f"cos_step5={cosines[-1]:.3f}"))
            if verbose:
                print(rows[-1])
    return rows


if __name__ == "__main__":
    run()
