"""Model: the latent-attention layers' causal softmax core's share of
its roofline. The least time the chip could take for a round's layer
calls of ``flops/<arch>.py:attention_flops`` over the bf16 peak or
``attention_bytes`` over the memory bandwidth, whichever is larger
(``q k^T`` over heads of 192 and ``p v`` over heads of 128 on the causal
pairs, forward and backward, nothing padded), over
``round_latent_attention_device_s``. None where the configuration counts
no latent layers. Source: device trace."""
from benchmark.harness import runner, scope_reduce


def read(ctx):
    flops = runner.load_by_name("flops", ctx["cell"]["config_file"]["arch"])
    if not hasattr(flops, "latent_flops"):
        return None
    return scope_reduce.mixer_roofline_pct(ctx, "lm.attention", "latent",
                                           "attention")
