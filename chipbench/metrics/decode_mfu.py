"""Operations the decoder's equations need for every token decoded inside
the traced window (each lane at its own context length), over the decode
program's device time times the chip's peak, in percent."""

from chipbench import flops, peaks


def read(run):
    if run.trace is None or not run.trace.programs["decode_fn"][1]:
        return None
    need = sum(flops.decode_token_flops(run.cfg, len(r.prompt) + j)
               for r in run.results for j, t in enumerate(r.token_times_s)
               if j >= 1 and t <= run.close_s)
    seconds = run.trace.programs["decode_fn"][0]
    return 100.0 * need / (seconds * peaks.peak(run.device_kind, "bf16_flops"))
