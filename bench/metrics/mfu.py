"""Whole program: model operations of every token processed in the window
(prompt tokens prefilled and tokens decoded, attention included) over the
window's seconds times the chips' bf16 peak, in percent.  Source: the
program tracer's chunk spans and decode rounds, and the host clock."""


def read(rec):
    w0, w1 = rec.window
    ops = sum(rec.family.chunk_step(rec.dims, s, n)[0]
              for s, n in rec.chunk_calls(w0, w1))
    ops += sum(rec.family.decode_step(rec.dims, lens)[0]
               for lens in rec.decode_steps(w0, w1))
    if not ops or not rec.peaks:
        return None
    return 100.0 * ops / (rec.seconds * rec.chips * rec.peaks["bf16_flops"])
