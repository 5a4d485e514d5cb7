"""Expert kernels: the held experts' fused int8 kernel calls in decode,
as a share of their roofline, in %.  The least time is what the chip
needs for the routed work (``bench/runners/lm_hybrid.py``: each held
expert's three calls per decode micro-step and MoE layer, at the mean
(token, held expert) pairs the engine counted in ``stats["expert_rows"]``,
each call the larger of operations at the bf16 peak and bytes at HBM
bandwidth); the device time is the self-time of the ops named by the
kernel (``epim_qmm``, the ``name`` and ``epim_kernel`` metadata of its
``pallas_call``) under the ``epim.moe`` scope of the decode program
(``jit(_decode_multi)``), from the op_name-path reduction of
``bench/spans.py``.  None for a program without the scope, the kernel
name or the counter."""
from bench import spans


def _decode_expert_kernels(path: str) -> bool:
    parts = path.split("/")
    return (parts[0] == "jit(_decode_multi)" and "epim.moe" in parts
            and any(p.startswith("epim_qmm") for p in parts))


def read(ctx, name):
    s = ctx["slice"]
    least = ctx["work"].get("expert_least_s")
    if not s.traced or not least:
        return None
    per_path, _ = spans.op_self_us(s.dir)
    spent = sum(us for path, us in per_path.items()
                if _decode_expert_kernels(path)) / 1e6
    return 100.0 * least / spent if spent > 0 else None
