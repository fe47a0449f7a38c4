"""Share of its roofline reached by the ``lstm_seq_stacked`` kernel, in
%: the least time the chip needs for each call (the larger of its FLOPs
over peak FLOP/s and its bytes over peak HBM bytes/s, counted by
``kernels/lstm_seq_stacked.py``) summed over the calls in the traced window,
over the kernel's device time there.  Nothing where the cell runs another
kernel or the trace does not show it."""

KERNEL = "lstm_seq_stacked"


def read(ctx):
    if ctx.trace is None or ctx.config.get("kernel") != KERNEL:
        return None
    k, cfg = ctx.layout.kernel(KERNEL), ctx.config
    shape = (cfg["hidden"], cfg["n_metrics"], cfg["window"])
    calls = ctx.kernel_calls(lambda name: k.call_targets(name, *shape))
    ns = sum(t for _, t in calls)
    if not calls or ns <= 0:
        return None
    # a call is charged the targets it serves, never padding rows
    needed = ctx.Z / ctx.trace.n_devices
    least = sum(max(n * k.flops_per_target(*shape)
                    / ctx.peaks["bf16_flops_per_s"],
                    n * k.bytes_per_target(*shape)
                    / ctx.peaks["hbm_bytes_per_s"])
                for n in (min(n, needed) for n, _ in calls))
    return 100.0 * least / (ns * 1e-9)
