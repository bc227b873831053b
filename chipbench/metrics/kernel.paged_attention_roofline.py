"""Share of its roofline that the paged decode-attention kernel reaches
in the traced window: for every decode forward there, the least time
the chip could take for the kernel's FLOPs and bytes (``counts.py``,
under the kernel's tile-skip rule, once per layer), summed, over the
kernel's device time in the trace."""
import counts

KERNEL = r"^decode_attention_paged\.\d+$"


def read(ctx):
    secs = ctx.trace.op_seconds(KERNEL)
    if not secs:
        return None
    a, e = ctx.arch, ctx.config["engine"]
    t0, t1 = ctx.run.trace_t
    n_kv_tiles = e["max_len"] // e["block"]
    least = 0.0
    for s in ctx.run.steps:
        if t0 <= s.t <= t1:
            w = counts.paged_attention(s.width, s.lens, e["block"],
                                       n_kv_tiles, a["n_heads"],
                                       a["n_kv_heads"], a["head_dim"])
            least += a["n_layers"] * w.seconds(
                ctx.peaks.bf16_flops, ctx.peaks.hbm_bytes_per_s)
    return 100.0 * least / secs if least else None
