#!/usr/bin/env python3
"""Serve qwen3-4b at its published width on a TPU, and check the result.

    python chip_smoke.py [--seed N]        # one chip
    python chip_smoke.py --chips 4         # disaggregated serving on four

Run it from the root of a checkout.  It is one process: build, serve,
compare, print.  The last line of standard output is one JSON object
naming the device.  Without an accelerator, or outside a checkout of
this repository, it exits non-zero and prints no result.

One chip.  qwen3-4b at full width (36 layers, d_model 2560, 32 heads,
8 KV heads, head_dim 128, d_ff 9728, vocab 151,936) with bfloat16
weights made from ``--seed`` serves ``SLOTS`` requests through
``PagedServeLoop.run`` with the Pallas kernels (``kernel_mode="pallas"``,
interpret mode off): page 16, s_max 2048, a 1,025-page KV pool of
2.42 GB.  The same requests are served again with ``kernel_mode="ref"``
on the chip, and the logits that each token was chosen from are
compared.

Four chips (``--chips 4``).  ``ShardedPagedServeLoop`` on
``make_serve_meshes(4)`` (two prefill chips, two decode chips, the KV
pool split over the decode pair) serves the same requests, then
``PagedServeLoop`` on one chip does, and the same comparison applies.
Every device's peak memory and share of the pool is printed.  This
phase runs ``kernel_mode="ref"``: XLA cannot partition a Mosaic kernel
over a mesh, and the sharded loop does not yet wrap its kernels in
``shard_map``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

ARCH = "qwen3-4b"
SLOTS = 8            # batch slots, one request each
PAGE = 16            # tokens per KV page
S_MAX = 2048         # per-slot horizon: 128 pages
CHUNK = 32           # prefill tokens per slot per step
MAX_NEW = 16         # generated tokens per request
PROMPT_MIN, PROMPT_MAX = 64, 1024
# The pool split over two decode chips needs a page count both divide;
# one page above the 1 + SLOTS * 128 the loop would size by default.
N_PAGES_SPLIT = 1026

# Largest |logit difference| allowed between two runs, over every token
# both runs chose from the same inputs.  Logits of these random weights
# have unit scale (final RMSNorm output times an N(0, 1/d) unembedding).
# The runs differ only in rounding: bfloat16 activations re-rounded
# after a differently ordered f32 softmax, carried through 36 layers.
# A kernel that reads a wrong page, position or mask moves logits by
# O(1), four times this bound.
LOGIT_TOL = 0.25


def fail(msg: str) -> None:
    print(f"chip_smoke: FAILED: {msg}", file=sys.stderr)
    sys.exit(1)


def gib(n: float) -> str:
    return f"{n / 2**30:.3f} GiB ({int(n)} B)"


def tree_bytes(tree) -> int:
    """Logical bytes of a pytree of arrays (all shards together)."""
    import jax
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


def make_requests(seed: int, vocab: int):
    """SLOTS prompts from PROMPT_MIN to PROMPT_MAX tokens (both ends
    always present), random tokens, MAX_NEW new tokens each."""
    import numpy as np
    from repro.runtime.serve_loop import Request
    rng = np.random.default_rng(seed)
    lens = [PROMPT_MIN, PROMPT_MAX,
            *rng.integers(PROMPT_MIN, PROMPT_MAX + 1, SLOTS - 2)]
    return [Request(rid=i, prompt=rng.integers(0, vocab, int(n)),
                    max_new=MAX_NEW) for i, n in enumerate(lens)]


def init_params(bundle, seed: int, out_shardings=None):
    """Weights from ``seed``, made on the device under one jit: eager
    init would hold float32 temporaries beside the weights."""
    import jax
    init = jax.jit(bundle.init, out_shardings=out_shardings)
    t = time.perf_counter()
    params = jax.block_until_ready(init(jax.random.PRNGKey(seed)))
    return params, time.perf_counter() - t


def compile_step(loop, chunk: int):
    """Ahead-of-time compile of the loop's step at ``chunk`` tokens per
    slot; returns (seconds, memory analysis).  The serve run then finds
    the program in the persistent compilation cache."""
    import jax.numpy as jnp
    b = loop.b
    args = (loop.params, loop.cache, jnp.zeros((b, chunk), jnp.int32),
            jnp.zeros(b, jnp.int32), jnp.zeros(b, jnp.int32),
            jnp.zeros((b, loop.npb), jnp.int32))
    t = time.perf_counter()
    compiled = loop._fwd.lower(*args).compile()
    return time.perf_counter() - t, compiled.memory_analysis()


def serve(label: str, loop, seed: int, vocab: int):
    import jax
    reqs = make_requests(seed, vocab)
    t = time.perf_counter()
    out = loop.run(reqs)
    jax.block_until_ready(loop.cache)
    dt = time.perf_counter() - t
    tokens = sum(len(v) for v in out.values())
    prompt = sum(len(r.prompt) for r in reqs)
    print(f"{label}: served {len(out)} requests, {prompt} prompt tokens, "
          f"{tokens} generated tokens in {dt:.2f} s "
          f"({loop.stats.prefill_steps} prefill steps, "
          f"{loop.stats.decode_steps} decode steps)", flush=True)
    if sorted(out) != [r.rid for r in reqs]:
        fail(f"{label}: results for {sorted(out)}")
    for rid, toks in out.items():
        if len(toks) != MAX_NEW or not all(0 <= t < vocab for t in toks):
            fail(f"{label}: request {rid} returned {toks}")
        rows = loop.token_logits[rid]
        if len(rows) != MAX_NEW:
            fail(f"{label}: request {rid} kept {len(rows)} logit rows")
    return out, loop.token_logits


def compare(label: str, out_a, logits_a, out_b, logits_b) -> None:
    """Compare two runs' logits over every token both chose from the
    same inputs: each request's first token, then every decode step up
    to and including the first at which the two runs' tokens part."""
    import numpy as np
    first = worst = 0.0
    agree = total = 0
    for rid in sorted(out_a):
        ta, tb = out_a[rid], out_b[rid]
        agree += sum(x == y for x, y in zip(ta, tb))
        total += len(ta)
        part = next((i for i, (x, y) in enumerate(zip(ta, tb)) if x != y),
                    len(ta) - 1)
        for i in range(part + 1):
            a, b = logits_a[rid][i], logits_b[rid][i]
            if not (np.isfinite(a).all() and np.isfinite(b).all()):
                fail(f"{label}: non-finite logits, request {rid} token {i}")
            d = float(np.max(np.abs(a - b)))
            worst = max(worst, d)
            if i == 0:
                first = max(first, d)
    ok = worst <= LOGIT_TOL
    print(f"{label}: max |first-token logit diff| {first:.6g}; "
          f"max |logit diff| over same-input tokens {worst:.6g} "
          f"(tolerance {LOGIT_TOL}) -> {'pass' if ok else 'FAIL'}; "
          f"generated tokens agreeing {agree}/{total} "
          f"({agree / total:.4f})", flush=True)
    if not ok:
        fail(f"{label}: logit difference {worst} above {LOGIT_TOL}")


def print_knobs() -> None:
    from repro.kernels.common import KNOBS_USED
    from repro.tune import cache_path
    path = cache_path()
    state = "present" if path.exists() else "absent: analytic defaults"
    print(f"tune cache: {path.relative_to(ROOT)} ({state})")
    for (op, dims), knobs in sorted(KNOBS_USED.items()):
        print(f"knobs: {op} dims={dims} {knobs}")


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


def one_chip(seed: int) -> None:
    import jax
    from repro.configs import get_config
    from repro.models.registry import build_model
    from repro.runtime.serve_loop import PagedServeLoop

    cfg = get_config(ARCH, param_dtype="bfloat16", kernel_mode="pallas")
    bundle = build_model(cfg)
    params, secs = init_params(bundle, seed)
    print(f"model: {ARCH} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} head_dim={cfg.hd} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab} params={cfg.param_dtype} "
          f"kernels={cfg.kernel_mode}")
    print(f"weights: {gib(tree_bytes(params))}, initialised in "
          f"{secs:.2f} s", flush=True)

    loop = PagedServeLoop(cfg, bundle, params, SLOTS, S_MAX, chunk=CHUNK,
                          page=PAGE, record_logits=True)
    pool = tree_bytes(loop.cache)
    print(f"kv pool: {loop.n_pages} pages x {PAGE} tokens, {gib(pool)}")
    for c in (CHUNK, 1):
        secs, ma = compile_step(loop, c)
        print(f"compile: step C={c} in {secs:.2f} s; arguments "
              f"{gib(ma.argument_size_in_bytes)}, outputs "
              f"{gib(ma.output_size_in_bytes)}, temporaries "
              f"{gib(ma.temp_size_in_bytes)}, aliased "
              f"{gib(ma.alias_size_in_bytes)}", flush=True)
        if ma.alias_size_in_bytes < pool:
            fail(f"step C={c} aliases {ma.alias_size_in_bytes} B, the "
                 f"pool is {pool} B: the cache is not donated")
    print(f"kv pool donated: each step aliases all {pool} B of it "
          "(device sizes add tile padding)")

    out_p, logits_p = serve("pallas", loop, seed, cfg.vocab)
    print_knobs()
    print(f"peak_bytes_in_use: {gib(peak_bytes(jax.devices()[0]))}",
          flush=True)
    del loop
    gc.collect()

    cfg_ref = dataclasses.replace(cfg, kernel_mode="ref")
    loop = PagedServeLoop(cfg_ref, build_model(cfg_ref), params, SLOTS,
                          S_MAX, chunk=CHUNK, page=PAGE, record_logits=True)
    out_r, logits_r = serve("ref", loop, seed, cfg.vocab)
    compare("pallas vs ref", out_p, logits_p, out_r, logits_r)


def four_chips(seed: int) -> None:
    import jax
    from repro.configs import get_config
    from repro.launch.mesh import make_serve_meshes
    from repro.models.registry import build_model
    from repro.parallel.sharding import ShardingRules, param_shardings
    from repro.runtime.mesh_serve import ShardedPagedServeLoop
    from repro.runtime.serve_loop import PagedServeLoop

    devices = jax.devices()
    if len(devices) < 4:
        fail(f"--chips 4 needs four devices, JAX sees {len(devices)}")
    cfg = get_config(ARCH, param_dtype="bfloat16", kernel_mode="pallas")
    bundle = build_model(cfg)
    meshes = make_serve_meshes(4)
    rules = ShardingRules(fsdp=False, seq_shard_cache=False)
    shapes = jax.eval_shape(bundle.init, jax.random.PRNGKey(seed))
    params, secs = init_params(
        bundle, seed, param_shardings(shapes, meshes.decode, rules))
    print(f"weights: {gib(tree_bytes(params))} per engine, made on the "
          f"decode chips in {secs:.2f} s", flush=True)

    loop = ShardedPagedServeLoop(cfg, bundle, params, SLOTS, S_MAX,
                                 meshes=meshes, rules=rules, chunk=CHUNK,
                                 page=PAGE, n_pages=N_PAGES_SPLIT,
                                 record_logits=True)
    del params
    pool = tree_bytes(loop.cache)
    print(f"kv pool: {loop.n_pages} pages, {gib(pool)}; staging pool "
          f"{loop.n_pages_pf} pages, {gib(tree_bytes(loop.cache_pf))}")
    out_s, logits_s = serve("sharded 2+2", loop, seed, cfg.vocab)
    print(f"sharded 2+2: {loop.stats.migrations} page migrations")
    held = {d: 0 for d in devices}
    for pool_tree in (loop.cache, loop.cache_pf):
        for leaf in jax.tree.leaves(pool_tree):
            for shard in leaf.addressable_shards:
                held[shard.device] += int(shard.data.nbytes)
    roles = {d: "prefill" for d in meshes.prefill.devices.flat}
    roles.update({d: "decode" for d in meshes.decode.devices.flat})
    for d in devices:
        print(f"device {d.id} ({roles.get(d, 'idle')}): peak_bytes_in_use "
              f"{gib(peak_bytes(d))}; pool bytes held {gib(held[d])}")
        if held[d] >= pool:
            fail(f"device {d.id} holds a whole pool ({held[d]} B)")
    print_knobs()
    del loop
    gc.collect()

    params, _ = init_params(bundle, seed)
    loop = PagedServeLoop(cfg, bundle, params, SLOTS, S_MAX, chunk=CHUNK,
                          page=PAGE, n_pages=N_PAGES_SPLIT,
                          record_logits=True)
    out_1, logits_1 = serve("one chip", loop, seed, cfg.vocab)
    compare("sharded 2+2 vs one chip", out_s, logits_s, out_1, logits_1)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the weights and the requests")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the disaggregated four-chip phase")
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no src/repro beside {Path(__file__).name}: run it from a "
             "checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))
    # tuned knobs come only from this file in the repository, or none
    os.environ["REPRO_TUNE_CACHE"] = str(
        ROOT / "benchmarks" / "tune_cache_tpu.json")

    import jax
    from repro.kernels.common import resolve_interpret
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        fail(f"JAX found no TPU (platform {dev.platform!r})")
    if resolve_interpret(None) is not False:
        fail("kernels would run in interpret mode on the chip")
    print(f"compile cache: {enable_compile_cache()}")

    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
