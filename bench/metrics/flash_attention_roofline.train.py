"""flash_attention_roofline.train: the Pallas flash-attention kernels'
share of their roofline, in percent (kernels/flash_attention.py).

The least time of every call in the traced window, from the FLOP and
byte functions of bench/flops.py at the shapes called (each chip's rows
of the batch, every head, the padded row length, head_dim), over the
device time of the kernels' events. The trace names a Pallas call only
by its HLO instruction, so the kernels are told by their signature
(``kernels``): a ``tpu_custom_call`` over head-major (B*H, S, D) bf16
blocks whose result holds the fp32 per-row statistics (B*H, S, 128) is
the forward; one returning one such block is the backward's dQ kernel,
two the dK/dV kernel (one call of each per backward). No such events,
nothing to read."""
import os
import sys

sys.path.append(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import flops  # noqa: E402

LANES = 128  # the lane-replicated width of the kernels' per-row statistics


def kernels(op_s, op_n, bh, s, d):
    """{"fwd" | "dq" | "dkv": (device seconds, calls)} over the trace's
    operations ``op_s`` / ``op_n`` (name -> seconds / calls)."""
    block, rows = f"bf16[{bh},{s},{d}]", f"f32[{bh},{s},{LANES}]"
    out = {k: [0.0, 0.0] for k in ("fwd", "dq", "dkv")}
    for name, sec in op_s.items():
        if 'custom_call_target="tpu_custom_call"' not in name:
            continue
        result = name.split(" custom-call(")[0]
        n = result.count(block)
        kind = ("fwd" if n == 1 and rows in result else
                "dkv" if n == 2 else
                "dq" if n == 1 and " = bf16[" in result else None)
        if kind:
            out[kind][0] += sec
            out[kind][1] += op_n[name]
    return {k: tuple(v) for k, v in out.items()}


def read(facts):
    t, c = facts["trace"], facts["config"]
    b, h = facts["rows"] // facts["chips"], c["num_attention_heads"]
    s, d = facts["seq_len"], c["head_dim"]
    k = kernels(t["op_s"], t["op_n"], b * h, s, d)
    spent = sum(sec for sec, _ in k.values())
    if not spent:
        return None
    least = (k["fwd"][1] * flops.least_seconds(flops.flash_attention_fwd(b, h, s, d), facts["peak"])
             + k["dq"][1] * flops.least_seconds(flops.flash_attention_bwd(b, h, s, d), facts["peak"]))
    return 100.0 * least / spent
