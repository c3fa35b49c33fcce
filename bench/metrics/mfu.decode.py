"""Operations of the live rows of the traced decode ticks over the decode
program's device time times the chip's peak, in %."""
from bench import readers
from bench import trace as tr


def read(run):
    t = run.get("trace") and tr.program_time(run["trace"], "_decode_fn")
    if not t:
        return None
    cnt, rank = readers.counts(run), run["traffic"]["users"]["rank"]
    flops = sum(cnt.forward_flops(run["config"], p, 1, rank, 1)
                for tick in readers.traced_ticks(run) for p in tick["decode"])
    return readers.share(flops / run["peak"]["bf16_flops"], t[0])
