"""The comparisons that decide ``correct``.

Each runs the configuration's plain reference (``bench/reference``) over
what the timed path produced, after the program's state is freed, and
returns the numbers that are held against the configuration's limits.
With ``control=True`` it also reads the controls: the same reference one
precision step down (``bench/reference/lowp.py``), which a limit has to
fail.
"""
from __future__ import annotations

import importlib
from typing import List, Tuple

import numpy as np


def _reference(name: str):
    return importlib.import_module(f"bench.reference.{name}")


def lm_gaps(cfg: dict, key_seed: int, sample: List[Tuple[list, list]],
            control: bool = False) -> dict:
    """Teacher-force the reference over each prompt plus its served tokens.
    At the position before each served token, the gap is the reference's
    best logit minus the served token's.  Returns the widest and the mean
    gap over the sample's served tokens (and, for the control, the same of
    the token the control puts first at each of those positions)."""
    import jax
    import jax.numpy as jnp
    ref = _reference(cfg["reference"])
    lowp = _reference("lowp")
    L = max(len(p) + len(t) - 1 for p, t in sample)
    rows = np.zeros((len(sample), L), np.int32)
    pos, tok = [], []
    for i, (p, t) in enumerate(sample):
        seq = list(p) + list(t[:-1])
        rows[i, :len(seq)] = seq
        for j, served in enumerate(t):
            pos.append((i, len(p) - 1 + j))
            tok.append(served)
    bi = jnp.asarray([b for b, _ in pos])
    si = jnp.asarray([s for _, s in pos])
    key = jax.random.PRNGKey(key_seed)
    init_key = jax.random.split(key, 3)[0]
    out = {"tokens": len(tok)}

    def gaps(choice, lg):
        g = jnp.max(lg, axis=-1) - lg[jnp.arange(len(tok)), choice]
        return float(jnp.max(g)), float(jnp.mean(g))

    lg = ref.logits(init_key, cfg, cfg["layers"], cfg["quant"], rows)[bi, si]
    out["max_gap"], out["mean_gap"] = gaps(jnp.asarray(tok), lg)
    for name, (dot, _) in (lowp.CONTROLS.items() if control else ()):
        lc = ref.logits(init_key, cfg, cfg["layers"], cfg["quant"], rows,
                        dot=dot)[bi, si]
        out[f"{name}_max_gap"], out[f"{name}_mean_gap"] = gaps(
            jnp.argmax(lc, axis=-1), lg)
    return out


def rel_l2(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def image_errors(cfg: dict, init_key, images, logits,
                 control: bool = False) -> dict:
    """Relative L2 distances from the reference's logits (of the whole
    batch: batch statistics couple its images): of the batch's mean logit
    vector (``mean_rel_l2``) and of all logits (``rel_l2``)."""
    ref = _reference(cfg["reference"])
    lowp = _reference("lowp")
    want = np.asarray(ref.logits(init_key, cfg["layers"], cfg["quant"],
                                 images))

    def errors(got, prefix=""):
        got = np.asarray(got)
        return {f"{prefix}mean_rel_l2": rel_l2(got.mean(0), want.mean(0)),
                f"{prefix}rel_l2": rel_l2(got, want)}
    out = errors(logits)
    for name, (dot, conv) in (lowp.CONTROLS.items() if control else ()):
        out.update(errors(ref.logits(init_key, cfg["layers"], cfg["quant"],
                                     images, conv=conv, dot=dot), name + "_"))
    return out
