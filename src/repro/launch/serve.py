"""Serving launcher: prefill a batch of prompts, decode N tokens.

    PYTHONPATH=src python -m repro.launch.serve --arch recurrentgemma_9b \
        --reduced --tokens 16

With ``--worp-topk K`` every request (batch row) additionally feeds its
decoded token ids into one stream of a batched SketchEngine -- the serving
tie-in the paper motivates (per-user token-frequency WOR samples, mergeable
across serving replicas) -- and the per-request top tokens print at the end.
``--sampler`` picks ANY sampler from the registry (onepass, twopass,
perfect, tv): the engine is sampler-generic, so serving analytics swap
samplers without code changes.

Token updates flow through the engine's pluggable DATA PLANE
(``--plane``): microbatches buffer host-side and dispatch through the
synchronous batched Pallas scatter plane (``sparse``, default), the
double-buffered worker-thread plane (``async``: the decode loop never
stalls on analytics dispatch), or the vmapped-jnp reference plane
(``dense``).  ``--worp-window W`` keeps the analytics over a sliding
window of the last W decode steps by RETRACTING (value -1 deletions)
tokens as they age out -- the signed-update workload the paper's turnstile
model exists for.

Multi-worker serving (``--workers N``): the decode stream is sharded
round-robin across N engine shards -- worker ``t % N`` ingests decode step
``t`` (and later retracts it when a window is set), modelling N serving
replicas that each observe a slice of every request's traffic.  Because
all shards derive identical per-stream seeds, their states are mergeable
stream-by-stream: at sampling time the shards aggregate through the
distributed reduction layer (host-form ``butterfly_allmerge`` for
power-of-two worker counts, ``tree_merge`` otherwise) and the aggregated
per-request samples equal a single worker that saw the whole stream --
the paper's composability, end to end.

Sharded analytics ingest (``--producers S``): each worker's analytics
plane becomes the ingestion pipeline's ``pipeline`` plane -- updates
partition per-key-hash across S sub-planes (each wrapping ``--plane``)
and collapse through the sampler's composable merge at sampling time,
the serving-side face of ``repro.data.ingest_pipeline``.
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ARCH_NAMES, get_config
from repro.core import sampler as core_sampler
from repro.distributed import codecs as wire_codecs
from repro.distributed import sharding as shd
from repro.engine import EngineConfig, SketchEngine, available_planes
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.models import transformer as T


def make_worker_engines(cfg: EngineConfig, workers: int, plane: str = "sparse",
                        flush_elems: int = 4096,
                        plane_opts: dict = None) -> list:
    """N mergeable engine shards: identical EngineConfig => identical
    per-stream hash/transform seeds, so stream b of every worker is a shard
    of request b's logical stream (the ``merge_with`` contract).
    ``plane_opts`` forwards plane-specific options (e.g. ``shards`` /
    ``subplane`` for the ingestion pipeline's ``pipeline`` plane)."""
    if workers < 1:
        raise ValueError(f"workers must be >= 1, got {workers}")
    return [SketchEngine(cfg, plane=plane, flush_elems=flush_elems,
                         plane_opts=plane_opts)
            for _ in range(workers)]


def aggregate_worker_states(workers: list, codec: str = "none"):
    """Drain every worker's data plane and reduce the shard states to the
    union state through the distributed merge layer: the host-form
    butterfly (hypercube XOR rounds) for power-of-two worker counts, the
    pairwise log-depth tree otherwise.  Stream-wise merging requires the
    shards to be mergeable -- identical configs, hence identical per-stream
    seeds (validated leaf-wise by the merge trees as well).  ``codec``
    names the wire codec each worker's state crosses to the aggregator
    (``repro.distributed.codecs``; ``none`` keeps today's bitwise path)."""
    if not workers:
        raise ValueError("aggregate_worker_states of no workers")
    ref = workers[0].cfg
    for i, w in enumerate(workers[1:], start=1):
        if w.cfg != ref:
            raise ValueError(
                f"worker {i} config differs from worker 0; shards must "
                f"share an EngineConfig to be mergeable")
    states = [w.flush().state for w in workers]
    return shd.merge_states(states, workers[0].ops.merge, codec=codec)


def sample_aggregated(workers: list, k: int, codec: str = "none"):
    """Per-request WOR samples over the UNION of all workers' ingested
    traffic (equals a single worker that saw the whole stream)."""
    merged = aggregate_worker_states(workers, codec=codec)
    return workers[0].sample_state(merged, k)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_NAMES)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--worp-topk", type=int, default=0,
                    help="track per-request token streams in a batched "
                         "SketchEngine and report the top-K WOR sample")
    ap.add_argument("--worp-p", type=float, default=1.0)
    ap.add_argument("--worp-window", type=int, default=0,
                    help="sliding window: only the last W decode steps count "
                         "toward the token analytics; older tokens are "
                         "retracted via turnstile deletions (0 = unbounded, "
                         "prompt included)")
    ap.add_argument("--sampler", default="onepass",
                    choices=core_sampler.available(),
                    help="registered sampler backing the token analytics "
                         "engine (see repro.core.sampler)")
    ap.add_argument("--plane", default="sparse",
                    choices=available_planes(),
                    help="data plane for the analytics ingest: sparse "
                         "(sync Pallas scatter), async (double-buffered "
                         "worker thread), dense (vmapped jnp reference)")
    ap.add_argument("--workers", type=int, default=1,
                    help="serving replicas: the decode stream shards "
                         "round-robin across N engines whose per-request "
                         "samples aggregate through the distributed merge "
                         "trees at reporting time")
    ap.add_argument("--producers", type=int, default=1,
                    help="analytics ingest producers per worker: S > 1 "
                         "wraps the selected --plane in the sharded "
                         "ingestion pipeline's 'pipeline' plane (per-key "
                         "hash partition across S sub-planes, collapsed "
                         "through the sampler merge at sampling time)")
    ap.add_argument("--codec", default="none",
                    choices=wire_codecs.available_codecs(),
                    help="wire codec for analytics state crossings: the "
                         "worker->aggregator merge and (with --producers) "
                         "the pipeline collapse encode through it; 'none' "
                         "keeps the bitwise fp32 path")
    args = ap.parse_args()
    enable_compile_cache()
    if args.worp_topk < 0:
        ap.error("--worp-topk must be >= 0")
    if args.worp_topk and args.worp_p <= 0:
        ap.error("--worp-p must be > 0 (samples by |freq|^p)")
    if args.worp_window < 0:
        ap.error("--worp-window must be >= 0")
    if args.workers < 1:
        ap.error("--workers must be >= 1")
    if args.producers < 1:
        ap.error("--producers must be >= 1")
    if args.producers > 1 and args.plane == "pipeline":
        ap.error("--producers already wraps --plane in the pipeline plane; "
                 "pick the SUB-plane (sparse/async/dense) with --plane")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.family == "encdec":
        raise SystemExit("use the enc-dec driver in examples/ for seamless")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    B, S = args.batch, args.prompt_len
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                          cfg.vocab_size, jnp.int32)}
    if cfg.family == "vlm":
        batch["patch_embeds"] = jax.random.normal(
            jax.random.PRNGKey(2), (B, cfg.num_patches, cfg.d_model),
            jnp.float32).astype(jnp.bfloat16) * 0.02
    logits, cache = jax.jit(
        lambda p, b: T.forward_prefill(p, b, cfg))(params, batch)
    # grow dense kv caches by the decode budget
    full = S + args.tokens + (cfg.num_patches if cfg.family == "vlm" else 0)

    def grow(x):
        if x.ndim >= 4 and x.shape[2] in (S, S + cfg.num_patches):
            pad = [(0, 0)] * x.ndim
            pad[2] = (0, full - x.shape[2])
            return jnp.pad(x, pad)
        return x
    cache = jax.tree_util.tree_map(grow, cache)
    step = jax.jit(lambda p, b: T.forward_decode(p, b, cfg))
    tok = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    pos0 = S + (cfg.num_patches if cfg.family == "vlm" else 0)
    engines: list = []
    window: list = []  # (worker_idx, token batch) still inside the window
    nstep = 0          # decode-step counter (round-robin worker routing)
    if args.worp_topk:
        # one engine stream per request, sharded over --workers replicas;
        # token updates buffer host-side and dispatch through the selected
        # data plane (turnstile ingest)
        ecfg = EngineConfig(
            num_streams=B, rows=5, width=max(256, 31 * args.worp_topk),
            candidates=4 * args.worp_topk, p=args.worp_p, seed=0x5EED,
            sampler=args.sampler, domain=cfg.vocab_size,
            num_samplers=max(4, args.worp_topk))
        plane, plane_opts = args.plane, None
        if args.producers > 1:
            plane = "pipeline"
            plane_opts = {"shards": args.producers, "subplane": args.plane,
                          "codec": args.codec}
        engines = make_worker_engines(ecfg, args.workers, plane=plane,
                                      plane_opts=plane_opts)

        def ingest_step(t):
            widx = nstep % len(engines)
            engines[widx].ingest(t, np.ones(t.shape, np.float32))
            if args.worp_window:
                window.append((widx, np.asarray(t)))
                if len(window) > args.worp_window:
                    # retraction: the aged-out step leaves the sliding
                    # window THROUGH THE WORKER THAT INGESTED IT, so every
                    # shard stream stays a sub-multiset of the union
                    oidx, old = window.pop(0)
                    engines[oidx].ingest(old,
                                         -np.ones(old.shape, np.float32))

        if not args.worp_window:
            # unbounded analytics include the prompt; windowed are decode-only
            engines[0].ingest(batch["tokens"],
                              np.ones(batch["tokens"].shape, np.float32))
        ingest_step(tok)
        nstep += 1
    outs = [np.asarray(tok)]
    for i in range(args.tokens):
        lg, cache = step(params, {"token": tok, "pos": jnp.int32(pos0 + i),
                                  "cache": cache})
        tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        outs.append(np.asarray(tok))
        if engines:
            ingest_step(tok)
            nstep += 1
    print("generated ids:")
    for row in np.concatenate(outs, axis=1):
        print(" ", row.tolist())
    if engines:
        # flushes every worker's pending ingests, merges the shard states
        # (butterfly/tree), then samples the aggregated per-request streams
        sample = sample_aggregated(engines, args.worp_topk,
                                   codec=args.codec)
        keys, freqs = np.asarray(sample.keys), np.asarray(sample.freqs)
        scope = (f"last {args.worp_window} decode steps" if args.worp_window
                 else "prompt + decode")
        wtag = f", {args.workers} workers" if args.workers > 1 else ""
        print(f"per-request top-{args.worp_topk} tokens over {scope} "
              f"(WOR ell_{args.worp_p} sample{wtag}):")
        for b in range(B):
            pairs = [f"{int(t)}:{f:.0f}" for t, f in zip(keys[b], freqs[b])
                     if t >= 0]
            print(f"  req {b}: {' '.join(pairs)}")


if __name__ == "__main__":
    main()
