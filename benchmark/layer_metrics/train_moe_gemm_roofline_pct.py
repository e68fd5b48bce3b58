"""Kernels: the grouped GEMM's work (forward, each rematerialised forward,
dlhs, drhs) against its roofline, over the step program's executions that
the trace holds WHOLE (``kernel_time.whole_steps``): a step's routed layers x
the calls a layer's rows pass through when one round holds them (12) x one
call over the rows THAT STEP's router made local (the step's own
``moe_local_rows``; the layout's padding counts as nothing, a second round
adds no rows) x hidden x expert width, the larger of FLOPs over the bf16 peak
and bytes over the HBM rate (``benchmark/latent_moe_flops.py``), over the
kernels' device time in the same steps."""

from benchmark import latent_moe_flops as lm


def read(obs):
    return lm.grouped_roofline(obs)
