"""The whole forecast step's share of the chip's peak, in %: the forecast
FLOPs the algorithm needs per tick (the cell's kernel, counted by
``kernels/<kernel>.py``) times the ticks per second of the traced window,
over the bf16 peak of the chips used."""


def read(ctx):
    t = ctx.trace
    if t is None or t.n_devices == 0:
        return None
    k, cfg = ctx.layout.kernel(ctx.config["kernel"]), ctx.config
    flops = ctx.Z * k.flops_per_target(cfg["hidden"], cfg["n_metrics"],
                                       cfg["window"])
    rate = t.ticks / (t.window_ns * 1e-9)
    return 100.0 * flops * rate / (ctx.peaks["bf16_flops_per_s"]
                                   * t.n_devices)
