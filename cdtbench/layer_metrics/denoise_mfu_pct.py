"""Step operations over step time and the chip's peak, in percent."""

from cdtbench import readers
from cdtbench.flops import peak_flops


def read(ctx):
    if not ctx.get("step_flops") or ctx["device"]["platform"] != "tpu":
        return None
    step_ms = readers.read("denoise_ms_per_step", ctx)
    if not step_ms:
        return None
    peak = peak_flops(ctx["device"]["kind"])
    return 100.0 * ctx["step_flops"] / (step_ms / 1000.0) / peak
