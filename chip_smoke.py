"""Chip smoke test: the epitome serving path and EPIM-ResNet-50 on a TPU.

    python3 chip_smoke.py               # one chip: serve + reference + ResNet
    python3 chip_smoke.py --chips 4     # four chips: sharded serving only

One process drives every chip it uses.  The script refuses to run anywhere
but on a TPU (it never falls back to the CPU) and needs the repository
around it (``src/``).  Weights and inputs are random, drawn from ``--seed``.

One chip, three phases:

* serve — ``EngineConfig(arch="rwkv6-7b", epitome="kernel-q3")`` at
  published widths (all 32 layers; int8-packed epitomes run by the fused
  Pallas kernels).  Four requests of 16, 64, 100 and 200 prompt tokens, so
  both the bucketed one-shot prefill and chunked prefill run, 16 new tokens
  each; every request must complete with 16 in-vocabulary tokens.
* reference — one prompt's prefill logits from the fused kernels against a
  float32 forward over the same params in which every epitomized layer
  multiplies by ``core.layers.effective_weight`` (the dequantized packed
  codes, reconstructed), under ``default_matmul_precision("highest")``.
* resnet — ``get_resnet("resnet50", "kernel-q3")``, prepacked, on 8 images
  of 224x224, against the same ``effective_weight`` reconstruct forward.

The decode step and the ResNet forward are compiled ahead of time and
their compiled text must hold ``tpu_custom_call``: the fused kernels ran as
Mosaic kernels, not in interpret mode.

``--chips 4`` runs only the sharded path: the same model served with the
column-parallel ``mesh="1,4"`` layout against a single-device run on one of
the chips.

Lines before the last are smoke output, not metrics.  The last line is one
JSON object, printed only when every phase passed:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

ARCH, EPITOME = "rwkv6-7b", "kernel-q3"
PROMPT_LENS = (16, 64, 100, 200)
MAX_NEW = 16
MAX_LEN = 256
# Fused kernels vs the float32 reference.  The served model computes in
# bfloat16 through 32 residual layers of random (untrained) weights, which
# amplify rounding: over 32 layers a plain jnp bf16 forward already differs
# from the float32 one by 0.13 rel L2 (CPU, d_model 256), the fused kernels
# by 0.11, and the error does not shrink with width (0.21, 0.11, 0.14 at
# d_model 64, 256, 1024).  A kernel reading the wrong (scale, zero) pair
# gives 1.2.  0.35 sits between the two; the run also prints the plain
# bf16 forward's error, the noise floor the kernels should match.
LM_REL_TOL = 0.35
# ResNet-50 runs float32 activations, but the chip multiplies float32 at
# bfloat16 input precision by default (dense convs and the kernels' dots)
# while the reference runs at "highest"; batch-stats BatchNorm
# renormalises every layer, so the error stays near a few bf16 roundings
# instead of compounding.  A wrong table or block gives O(1).
RESNET_REL_TOL = 0.1
# Sharded vs single-device serving: the column-parallel layout shards only
# output dims and the fused kernels run whole on every chip, but XLA fuses
# and lays out the sharded program differently, and any rounding that
# moves is amplified through the 32 layers exactly like the bf16 floor
# above.  Same tolerance, same reason.
SHARD_REL_TOL = LM_REL_TOL

def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


def prompts(seed: int, vocab: int, lens=PROMPT_LENS):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).tolist() for n in lens]


def peak_bytes(device) -> int:
    return int(device.memory_stats()["peak_bytes_in_use"])


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------
def build_engine(*, seed: int, mesh, arch: str = ARCH, smoke: bool = False,
                 max_len: int = MAX_LEN):
    from repro.launch.engine import EngineConfig
    return EngineConfig(arch=arch, epitome=EPITOME, smoke=smoke, mesh=mesh,
                        capacity=4, max_len=max_len, decode_block=4,
                        prefill_chunk=64, seed=seed).build()


def serve(engine, prompt_list, max_new: int = MAX_NEW):
    """Submit every prompt, drain, check completions; returns (tokens per
    request, drain seconds)."""
    from repro.launch.engine import Request
    t0 = time.perf_counter()
    handles = [engine.submit(Request(prompt=p, max_new_tokens=max_new))
               for p in prompt_list]
    engine.drain()
    dt = time.perf_counter() - t0
    vocab = engine.cfg.vocab
    out = []
    for h, p in zip(handles, prompt_list):
        check(h.done(), f"request {h.request_id} did not complete")
        toks = h.result().tokens
        check(len(toks) == max_new,
              f"request of {len(p)} tokens emitted {len(toks)} != {max_new}")
        check(all(0 <= t < vocab for t in toks),
              f"request of {len(p)} tokens emitted ids outside [0, {vocab})")
        out.append(toks)
    return out, dt


def prefill_logits(params, cfg, prompt, *, max_len: int = MAX_LEN):
    """Last-position logits of one prompt through lm.prefill (float32)."""
    import jax
    import jax.numpy as jnp
    from repro.models import lm
    fn = jax.jit(lm.prefill, static_argnames=("cfg",))
    state = lm.init_decode_state(cfg, 1, max_len)
    logits, _ = fn(params, jnp.asarray([prompt], jnp.int32), state, cfg=cfg)
    return np.asarray(jax.device_get(logits[0, -1]), np.float32)


def reference_config(cfg):
    """The served config with float32 compute and every epitomized layer in
    'reconstruct' mode: each multiplies by effective_weight, i.e. the
    dequantized packed codes the fused kernel reads, reconstructed to W."""
    from repro.models import lm
    layers = tuple((name, dataclasses.replace(lc, mode="reconstruct"))
                   for name, lc in lm.lm_layer_configs(cfg).items()
                   if lc.is_epitome)
    return dataclasses.replace(cfg, compute_dtype="float32",
                               layer_config=layers)


def reference_logits(params, cfg, prompt, *, max_len: int = MAX_LEN):
    import jax
    with jax.default_matmul_precision("highest"):
        return prefill_logits(params, reference_config(cfg), prompt,
                              max_len=max_len)


def decode_step_text(engine) -> str:
    """Compile lm.decode_step at the engine's slot width over its served
    params, run it once, and return the compiled text."""
    import jax
    import jax.numpy as jnp
    from repro.models import lm
    cfg, B = engine.cfg, engine.capacity
    state = lm.init_decode_state(cfg, B, engine.seq_len)
    tok = jnp.zeros((B, 1), jnp.int32)
    pos = jnp.zeros((B,), jnp.int32)
    step = jax.jit(lm.decode_step, static_argnames=("cfg",))
    compiled = step.lower(engine.serve_params, state, tok, pos,
                          cfg=cfg).compile()
    logits, _ = compiled(engine.serve_params, state, tok, pos)
    check(bool(jnp.all(jnp.isfinite(logits))), "decode-step logits not finite")
    return compiled.as_text()


def resnet_phase(seed: int, *, arch: str = "resnet50", batch: int = 8,
                 size: int = 224):
    """Prepacked kernel-q3 ResNet forward vs its effective_weight reconstruct
    forward.  Returns (rel err, compiled text, logits shape)."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_resnet
    from repro.models.resnet import ResNetModel
    model = get_resnet(arch, EPITOME)
    k_init, k_img = jax.random.split(jax.random.PRNGKey(seed))
    params = model.prepack(model.init(k_init))
    images = jax.random.normal(k_img, (batch, size, size, 3), jnp.float32)
    compiled = jax.jit(model.apply).lower(params, images).compile()
    y = np.asarray(compiled(params, images))
    ref_model = ResNetModel(model.layers, model.specs,
                            quant_bits=model.quant_bits, mode="reconstruct")
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(ref_model.apply)(params, images))
    check(np.isfinite(y).all(), "ResNet logits not finite")
    return rel_err(y, ref), compiled.as_text(), y.shape


# ---------------------------------------------------------------------------
# Runs
# ---------------------------------------------------------------------------
def one_chip(seed: int, *, arch: str = ARCH, smoke: bool = False,
             resnet: str = "resnet50", image: int = 224) -> None:
    import jax
    dev = jax.devices()[0]

    t0 = time.perf_counter()
    engine = build_engine(seed=seed, mesh="", arch=arch, smoke=smoke)
    jax.block_until_ready(engine.serve_params)
    cfg = engine.cfg
    log(f"serve: built {arch} {EPITOME} ({cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, d_ff {cfg.d_ff}, vocab {cfg.vocab}) in "
        f"{time.perf_counter() - t0:.1f}s; mesh {dict(engine.mesh.shape)}")
    plist = prompts(seed, engine.cfg.vocab)
    _, first = serve(engine, plist)
    st = engine.stats
    log(f"serve: {st['completed']}/{len(plist)} requests completed, "
        f"{MAX_NEW} tokens each; first drain {first:.1f}s (includes "
        f"compiling {st['prefill_traces']} prefill and "
        f"{st['decode_traces']} decode programs)")
    _, warm = serve(engine, plist)
    st = engine.stats
    log(f"serve: warm drain {warm:.2f}s; compile ~{first - warm:.1f}s; "
        f"decode dispatches {st['decode_steps']}, micro-steps "
        f"{st['decode_micro_steps']}; prefill chunks {st['prefill_chunks']}")
    check(st["completed"] == 2 * len(plist), "not every request completed")

    text = decode_step_text(engine)
    n = text.count("tpu_custom_call")
    log(f"serve: decode step compiled with {n} tpu_custom_call kernel calls")
    check(n > 0, "decode step holds no Mosaic kernel")

    prompt = plist[1]
    got = prefill_logits(engine.serve_params, engine.cfg, prompt)
    want = reference_logits(engine.serve_params, engine.cfg, prompt)
    floor = prefill_logits(engine.serve_params, dataclasses.replace(
        reference_config(engine.cfg), compute_dtype="bfloat16"), prompt)
    check(np.isfinite(got).all() and np.isfinite(want).all(),
          "prefill logits not finite")
    err = rel_err(got, want)
    log(f"reference: prefill logits of a {len(prompt)}-token prompt, fused "
        f"kernels vs float32 effective_weight forward: rel L2 err "
        f"{err:.3e} (tol {LM_REL_TOL}; the same forward in plain jnp bf16: "
        f"{rel_err(floor, want):.3e}), max |diff| "
        f"{np.abs(got - want).max():.3e} of max |ref| "
        f"{np.abs(want).max():.3e}, argmax agree "
        f"{int(got.argmax() == want.argmax())}")
    check(err <= LM_REL_TOL, f"LM logits rel err {err} > {LM_REL_TOL}")
    log(f"serve: peak_bytes_in_use {peak_bytes(dev)}")
    del engine
    gc.collect()

    err, text, shape = resnet_phase(seed, arch=resnet, size=image)
    n = text.count("tpu_custom_call")
    log(f"resnet: {resnet} {EPITOME} on 8x{image}x{image} images -> logits "
        f"{shape}; {n} tpu_custom_call kernel calls; rel L2 err vs "
        f"reconstruct {err:.3e} (tol {RESNET_REL_TOL})")
    check(n > 0, "ResNet forward holds no Mosaic kernel")
    check(err <= RESNET_REL_TOL, f"ResNet rel err {err} > {RESNET_REL_TOL}")
    log(f"resnet: peak_bytes_in_use {peak_bytes(dev)}")


def four_chips(seed: int, *, arch: str = ARCH, smoke: bool = False) -> None:
    """Column-parallel sharded serving on a (1, 4) mesh vs one chip."""
    import jax
    from repro.models.common import set_mesh
    n = len(jax.devices())
    check(n >= 4, f"--chips 4 needs four devices, found {n}")

    single = build_engine(seed=seed, mesh=None, arch=arch, smoke=smoke)
    plist = prompts(seed, single.cfg.vocab)
    prompt = plist[1]
    want = prefill_logits(single.serve_params, single.cfg, prompt)
    toks1, _ = serve(single, plist)
    log(f"sharded: single-device reference on {jax.devices()[0]}")
    del single
    gc.collect()

    t0 = time.perf_counter()
    sharded = build_engine(seed=seed, mesh="1,4", arch=arch, smoke=smoke)
    jax.block_until_ready(sharded.serve_params)
    log(f"sharded: built on mesh {dict(sharded.mesh.shape)} in "
        f"{time.perf_counter() - t0:.1f}s")
    groups = sharded.serve_params["groups"]
    eq = [leaf for path, leaf in
          jax.tree_util.tree_flatten_with_path(groups)[0]
          if getattr(path[-1], "key", None) == "Eq"]
    check(bool(eq), "no packed Eq leaves in the served tree")
    on4 = sum(len(leaf.sharding.device_set) == 4 for leaf in eq)
    split = sum(not leaf.sharding.is_fully_replicated for leaf in eq)
    head = sharded.serve_params["head"]
    log(f"sharded: {on4}/{len(eq)} packed Eq leaves span 4 devices, {split} "
        f"of them split over 'model' (fan-in layers put their columns on "
        f"'data', size 1 here); head split: "
        f"{not head.sharding.is_fully_replicated}")
    check(on4 == len(eq), "some packed Eq leaves do not span 4 devices")
    check(split > 0, "no packed Eq leaf is split over the 4 devices")
    check(len(head.sharding.device_set) == 4
          and not head.sharding.is_fully_replicated,
          "the head is not split over 4 devices")

    got = prefill_logits(sharded.serve_params, sharded.cfg, prompt)
    err = rel_err(got, want)
    toks4, _ = serve(sharded, plist)
    agree = np.mean([a == b for t1, t4 in zip(toks1, toks4)
                     for a, b in zip(t1, t4)])
    log(f"sharded: prefill logits vs single device rel L2 err {err:.3e} "
        f"(tol {SHARD_REL_TOL}), max |diff| {np.abs(got - want).max():.3e}; "
        f"greedy tokens agree {agree:.4f} over "
        f"{sum(len(t) for t in toks1)} tokens")
    check(np.isfinite(got).all(), "sharded logits not finite")
    check(err <= SHARD_REL_TOL, f"sharded rel err {err} > {SHARD_REL_TOL}")
    for d in jax.devices()[:4]:
        log(f"sharded: {d} peak_bytes_in_use {peak_bytes(d)}")
    set_mesh(None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve + reference + ResNet on one chip; "
                         "4: sharded serving on a (1, 4) mesh only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    if dev.platform != "tpu":
        log("no TPU found; this smoke test runs only on the chip")
        return 1
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        log(f"no repro package under {src}; run from a checkout")
        return 1
    log("the lines below are smoke output, not metrics")

    sys.path.insert(0, src)
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")

    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
