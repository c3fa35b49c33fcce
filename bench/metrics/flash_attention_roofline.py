"""Share of its roofline that flash attention reaches in the traced training
steps, in %: every call of the forward and both backward kernels, each
counted causal and GQA-aware (``bench/roofline/flash_attention.py``), its
least time max(operations / peak, bytes / bandwidth) summed over the calls
the trace shows, over those kernels' device time."""
from bench import core, readers
from bench import trace as tr

# The trace names a Pallas kernel only by its HLO text, so each kernel is
# found by its results: the forward gives (O bf16, log-sum-exp f32 with a
# trailing 1), dq one bf16 (B, H, S, Dh), dkv two f32 (B, H, S, Dh) (per
# query head, summed over each group afterwards).
_4D = r"\[\d+,\d+,\d+,\d+\]\S*"
PARTS = {"fwd": rf"= \(bf16{_4D}, f32\[\d+,\d+,\d+,1\]\S*\) custom-call\(",
         "bwd_dq": rf"= bf16{_4D} custom-call\(",
         "bwd_dkv": rf"= \(f32{_4D}, f32{_4D}\) custom-call\("}


def read(run):
    t = run.get("trace")
    if run["kind"] != "train" or not t:
        return None
    m, pk, mix = readers.dims(run), run["peak"], run["traffic"]
    cost = core.module("roofline", "flash_attention", run["root"]).cost
    least = spent = 0.0
    for part, pat in PARTS.items():
        hit = tr.op_time(t, pat)
        if not hit:
            continue
        f, b = cost(part, mix["batch"], mix["seq"], m["H"], m["K"], m["Dh"])
        least += hit[1] * max(f / pk["bf16_flops"], b / pk["hbm_bw"])
        spent += hit[0]
    return readers.share(least, spent)
