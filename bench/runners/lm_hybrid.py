"""Hybrid language-model cells (Jamba: Mamba, attention, dense and MoE
FFNs): served by the program's engine exactly as ``lm_engine`` serves
RWKV; only the work of a traced slice is counted differently.

Work, the same whatever implements it, from the configuration file alone
(``bench/work.py``'s conventions: an epitome call on T rows is 2 T m N
operations and moves its folded input and output at the compute dtype,
its codes at their bit width and one float32 (scale, zero) pair per
tile; a dense weight is 2 T M N and is read at the parameter dtype):

* Mamba, per token: in_proj, x_proj, dt_proj and out_proj; the causal
  conv (2 d_conv d_inner); the scan, 6 d_inner d_state (dA, dt x B, the
  update's multiply and add, and y's multiply and add); its float32 state
  ``h`` read and written once.
* Attention, per token at context c: the four projections and 4 c H hd
  (scores and values); its bf16 K and V read for the c positions.
* MoE, per token: the router (2 d E over all experts, float32) and, per
  (token, held expert) pair it routed, the expert's three projections;
  every held expert's codes are read once.
* Dense FFN: the three projections.

The MoE layers' (token, held expert) pairs come from the engine's counter
``stats["expert_rows"]``; a decode micro-step's expert calls run at the
mean routed rows per held expert (the counter's increase over micro-steps,
MoE layers and held experts).
"""
from __future__ import annotations

from typing import Dict, Optional

from bench import work
from bench.runners.lm_engine import Cell as _EngineCell

DENSE_BYTES = work.DTYPE_BYTES


def _site_work(cfg: dict, name: str, T: float):
    """(ops, bytes) of one projection site called on T rows."""
    lay = next(l for l in cfg["layers"] if l["name"] == name)
    act = DENSE_BYTES[cfg["compute_dtype"]]
    if lay["spec"]:
        return (work.epitome_ops(T, lay["spec"]),
                work.epitome_bytes(T, lay["spec"], cfg["quant"],
                                   cfg["compute_dtype"]))
    M, N = lay["M"], lay["N"]
    return (2 * T * M * N,
            T * M * act + T * N * act + M * N * DENSE_BYTES[cfg["param_dtype"]])


def _sum(*pairs):
    return (sum(p[0] for p in pairs), sum(p[1] for p in pairs))


def mixer_work(cfg: dict, i: int, T: float, ctx: float = 0.0):
    """(ops, bytes) of layer ``i``'s mixer on T tokens (each at context
    ``ctx`` for attention)."""
    pre = f"L{i}/mixer/"
    kind = cfg["pattern"][i]
    if kind == "mamba":
        di = cfg["mamba_expand"] * cfg["d_model"]
        ds, dc = cfg["mamba_d_state"], cfg["mamba_d_conv"]
        ops, nbytes = _sum(*(_site_work(cfg, pre + n, T) for n in
                             ("in_proj", "x_proj", "dt_proj", "out_proj")))
        return (ops + T * (2 * dc * di + 6 * di * ds),
                nbytes + T * 2 * di * ds * 4)
    H, Hk, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    ops, nbytes = _sum(*(_site_work(cfg, pre + n, T) for n in
                         ("wq", "wk", "wv", "wo")))
    kv = DENSE_BYTES[cfg["compute_dtype"]]
    return (ops + T * 4 * ctx * H * hd, nbytes + T * ctx * 2 * Hk * hd * kv)


def expert_calls(cfg: dict, i: int, T: float):
    """(ops, bytes) of each of one expert's three calls on T rows."""
    return [_site_work(cfg, f"L{i}/ffn/{n}", T)
            for n in ("w_gate", "w_up", "w_down")]


def ffn_work(cfg: dict, i: int, T: float, routed: float = 0.0):
    """(ops, bytes) of layer ``i``'s FFN on T tokens; an MoE layer's
    experts at ``routed`` (token, held expert) pairs."""
    if cfg["ffn_pattern"][i] == "dense":
        return _sum(*expert_calls(cfg, i, T))
    d, E = cfg["d_model"], cfg["n_experts"]
    lo, hi = cfg["experts_held"]
    ops, nbytes = _sum(*expert_calls(cfg, i, routed))
    _, codes = _sum(*expert_calls(cfg, i, 0))      # every held expert's
    return (2 * T * d * E + ops,
            T * d * DENSE_BYTES[cfg["compute_dtype"]] + d * E * 4
            + nbytes + (hi - lo - 1) * codes)


def moe_layers(cfg: dict):
    return [i for i, f in enumerate(cfg["ffn_pattern"]) if f == "moe"]


class Cell(_EngineCell):
    def slice_work(self, t_a: float, t_b: float, peaks: dict
                   ) -> Dict[str, Optional[float]]:
        """Decode work between two instants of the window: tokens retired
        and decode micro-steps run; per layer kind, the operations and
        bytes of those micro-steps (each at the mean live rows, context
        and routed pairs per step: weights are read once a step); the MoE
        layers' routed (token, held expert) pairs (``stats["expert_rows"]``,
        None for a program without the counter); and the least time of the
        held experts' kernel calls at those rows."""
        cfg, c = self.cfg, self.counters
        groups = cfg["n_layers"] // len(cfg["pattern"])
        moe = moe_layers(cfg)
        tokens, ctx_sum = 0, 0
        for s in self.sent:
            P = len(s.req.prompt)
            for j, t in enumerate(s.all_times()[1:], start=1):
                if t_a <= t <= t_b:
                    tokens += 1
                    ctx_sum += P + j
        a, b = c.get("slice_start", {}), c.get("slice_end", {})
        steps = b.get("decode_micro_steps", 0) - a.get("decode_micro_steps", 0)
        rows = (b["expert_rows"] - a["expert_rows"]
                if "expert_rows" in a and "expert_rows" in b else None)
        out = {"decode_tokens": tokens, "decode_micro_steps": steps,
               "expert_rows": rows, "ops": {}, "bytes": {},
               "expert_least_s": None}
        if not steps:
            return out
        T, ctx = tokens / steps, ctx_sum / max(tokens, 1)
        routed = (rows or 0) / (steps * groups * len(moe)) if moe else 0.0
        for i, kind in enumerate(cfg["pattern"]):
            for k, (o, n) in ((kind, mixer_work(cfg, i, T, ctx)),
                              (cfg["ffn_pattern"][i],
                               ffn_work(cfg, i, T, routed))):
                out["ops"][k] = out["ops"].get(k, 0) + steps * groups * o
                out["bytes"][k] = out["bytes"].get(k, 0) + steps * groups * n
        lo, hi = cfg["experts_held"]
        if rows and moe:
            calls = steps * groups * len(moe) * (hi - lo)
            out["expert_least_s"] = calls * sum(
                work.least_seconds(o, n, peaks)
                for o, n in expert_calls(cfg, moe[0], rows / calls))
        return out
