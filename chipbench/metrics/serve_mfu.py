"""Model FLOPs of every prompt and output token processed in the window
(matmuls, attention at its real context, the unembed where the engine
computes it) over the window times the chip's peak bf16 FLOP/s."""
from chipbench import counts, peaks
from chipbench.readings import window_steps


def read(run):
    conf, P = run.cell.config, run.cell.traffic["prompt_len"]
    flops = sum(st.admitted * counts.prefill_flops(conf, P)
                + counts.decode_flops(conf, st.contexts)
                for st in window_steps(run))
    if not flops:
        return None
    pk = peaks.peak(run.device_kind)
    return 100.0 * flops / (run.seconds * pk.bf16_flops)
