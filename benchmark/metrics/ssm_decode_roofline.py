"""Share of its roofline that the state update of a decode step reaches, in
percent: device time of ``mxtpu_ssm_decode`` events in the traced stretch
against the larger of operations/peak and bytes/peak of reading and writing
the state of every live sequence once a state layer a step (it is bound by
bytes).

A trace may lose programs (one traced run in five kept 104 of 238 decode
programs, PERF.md section 7): the kernel's events are counted against one a
state layer a decode step of the stretch, the work is scaled by the share
kept, and under half kept nothing is returned."""
import _serve
import _ssm
from harness import peaks, roofline
from harness.device import log


def read(ctx):
    span = _serve.traced_span(ctx) if ctx["kind"] == "serve" else None
    layers = _ssm.state_layers(ctx["config"]) if span else 0
    work = _ssm.decoded(ctx, span) if layers else None
    if work is None:
        return None
    steps, rows = work
    secs, calls = ctx["trace"].kernel_seconds("mxtpu_ssm_decode")
    if not calls or secs <= 0 or not rows:
        return None
    kept = calls / float(steps * layers)
    log(f"traced stretch: {calls} state-update kernels for {steps} decode "
        f"steps of {layers} state layers ({100 * kept:.0f}% kept), "
        f"{rows / steps / layers:.1f} live a step, "
        f"{secs / calls * 1e6:.1f} us a kernel")
    if kept < 0.5:
        return None
    c = ctx["config"]
    flops, nbytes = _ssm.state_update(
        rows * min(kept, 1.0), c["mamba_n_heads"], c["mamba_d_head"],
        c["mamba_d_state"], {"float32": 4, "bfloat16": 2}[c["state_dtype"]])
    least = roofline.seconds(flops, nbytes, peaks.peak(ctx["device_kind"]))
    return 100.0 * least / secs
