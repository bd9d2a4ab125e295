"""Shared by the two flash metrics: find the flash kernels' executed calls
in the trace and say which of the three each is.

A Mosaic call is an ``XLA Ops`` event whose HLO text holds
``custom_call_target="tpu_custom_call"``. The three kernels of
kernels/flash_attention.py carry no ``name=`` yet, so they are told apart by
their signature: the forward returns ``(out [.., T, D], lse [.., T, 1])`` from
four operands, dQ returns one ``[.., T, D]`` array from seven, dK/dV returns
two ``[.., T, D]`` arrays from seven. Leading dims (vmapped clients, batch x
heads) multiply into the row count.
"""

import math
import re

SHAPE = re.compile(r"(?:bf16|f16|f32)\[([\d,]+)\]")
ITEM = {"bf16": 2, "f16": 2, "f32": 4}


def classify(hlo: str):
    """(kind, rows, t, d, item_bytes) of one Mosaic call, or None if its
    signature is none of the three flash kernels'."""
    if "tpu_custom_call" not in hlo or " custom-call(" not in hlo:
        return None
    head, args = hlo.split(" custom-call(", 1)
    result = head.split(" = ", 1)[1] if " = " in head else head
    outs = [tuple(int(x) for x in m.split(",")) for m in SHAPE.findall(result)]
    n_in = len(SHAPE.findall(args.split("custom_call_target", 1)[0]))
    if not outs or len(outs[0]) < 3:
        return None
    *lead, t, d = outs[0]
    item = ITEM[re.search(r"(bf16|f16|f32)\[", result).group(1)]
    rows = math.prod(lead)
    if len(outs) == 2 and outs[1][-1] == 1 and n_in == 4:
        kind = "fwd"
    elif len(outs) == 1 and n_in == 7:
        kind = "dq"
    elif len(outs) == 2 and outs[1] == outs[0] and n_in == 7:
        kind = "dkv"
    else:
        return None
    return kind, rows, t, d, item


def calls(trace):
    """[(kind, rows, t, d, item, seconds)] of every executed flash call in
    the window, first chip."""
    if not trace.devices:
        return []
    lo, hi = trace.window
    lane = trace.devices[sorted(trace.devices)[0]]
    out, seen = [], {}
    for e in lane.ops:
        if e.end <= lo or e.start >= hi or "tpu_custom_call" not in e.name:
            continue
        if e.name not in seen:
            seen[e.name] = classify(e.name)
        sig = seen[e.name]
        if sig is not None:
            out.append((*sig, (e.end - e.start) / 1e9))
    return out
