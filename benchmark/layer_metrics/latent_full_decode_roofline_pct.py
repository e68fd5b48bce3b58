"""Kernels: latent attention over a row's WHOLE context, the decode path
(``latent_attention_decode_full``: the rows of one token, in the decode step
and riding in a mixed step) against its roofline: a row's context of 576
values a key read once a latent layer (``latent_keys_single`` of the steps'
spans) at the HBM rate, or the mathematics' own ``q . k`` and ``p . v`` a
(query, key) pair a head at the bfloat16 peak, whichever is larger
(``benchmark/kda_flops.py``), over the device time under the scope.  The
keys are those of the steps that ended inside the traced interval
(``kda_flops.kda_steps``): contexts climb through a window.  The kernel
fetches the pool's rows whole (640 wide, 64 of them for 576), so it cannot
pass 90 % of this."""

from benchmark import kda_flops

SCOPES = ("latent_attention_decode_full",)
KEYS = "latent_keys_single"


def read(obs):
    got = kda_flops.traced(obs)
    if got is None:
        return None
    t, model, peaks = got
    least = taken = 0.0
    for kind, program in (("mixed", "jit_mixed_step"),
                          ("decode", "jit_decode_step")):
        steps = [a for a in kda_flops.kda_steps(obs, kind) if KEYS in a]
        n = kda_flops.steps_traced(t, model, program)
        if not steps or not n:
            continue
        keys = sum(a[KEYS] for a in steps) / len(steps)
        least += n * max(
            kda_flops.attention_bytes(model, keys) / peaks["hbm_bytes_per_s"],
            kda_flops.attention_flops(model, keys)  # one query a row
            / peaks["bf16_flops_per_s"])
        taken += kda_flops.scope_seconds(t, SCOPES, program)
    if not taken or not least:
        return None
    return 100.0 * least / taken
