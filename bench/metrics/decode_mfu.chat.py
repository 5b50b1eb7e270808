"""Device: model FLOPs of the decode steps (weight GEMMs and sequence
mixing, live slots only, from the configuration's shapes) over their
device time times the chip's peak bf16 FLOP/s, in %."""
from bench import readers


def read(run):
    issues = readers.issues_in_slice(run)
    if not issues:
        return None
    flops = sum(run.family.decode_flops(run.shape, n, ctx)
                for _t, n, ctx in issues) / len(issues)
    step_s = readers.program_ms(run, readers.DECODE) * 1e-3
    return 100.0 * flops / (step_s * run.peak["bf16_flops"])
