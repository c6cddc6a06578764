"""The flat tail kernels' share of their HBM roofline on device 0: the
bytes their algorithms must move over the traced steps (`counts`: AdamW
reads p, g, m, v and writes p, m, v; under FSDP-Norm the fused statistics
read g_j and g), at the chip's peak bandwidth, over the summed device time
of those kernels' events.  Silent where no tail kernel ran.  Moves
`train_tokens_per_s`.

The device trace names a Pallas kernel only as a `tpu_custom_call`; the
tail's AdamW is the one whose four operands (p, g, m, v) are 1-D flat
buffers, the fused statistics the one whose two are.  A family the cell's
step does not count (`ctx.tail_bytes_per_step`) is left out."""

import re

MOSAIC = 'custom_call_target="tpu_custom_call"'
OPERANDS = re.compile(r"custom-call\((.*?)\), custom_call_target")
FLAT = re.compile(r"\b(?:f32|bf16)\[\d+\]\{0")
FAMILY_BY_FLAT_OPERANDS = {4: "adamw", 2: "stats"}


def family(name):
    if MOSAIC not in name:
        return None
    m = OPERANDS.search(name)
    return FAMILY_BY_FLAT_OPERANDS.get(
        len(FLAT.findall(m.group(1))) if m else -1)


def read(ctx):
    ops = ctx.device_ops.get(0) or []
    secs, seen = 0.0, set()
    for e in ops:
        fam = family(e["name"])
        if fam is not None and fam in ctx.tail_bytes_per_step:
            secs += e["dur_ns"] * 1e-9
            seen.add(fam)
    if secs <= 0:
        return None
    nbytes = sum(ctx.tail_bytes_per_step[f] for f in sorted(seen)) * \
        ctx.traced_steps
    return 100.0 * nbytes / ctx.peak["hbm_bytes_per_s"] / secs
