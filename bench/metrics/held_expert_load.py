"""Tokens each held expert takes a wave: the assignments of the active
slots' tokens to experts this chip holds (the session's
``stats["expert_tokens"]``) over the traced span's waves, divided by the
waves, the MoE layers and the held experts. A program that keeps no such
counter, or a configuration without held experts, reads nothing."""


def read(ctx):
    before, after = ctx.stats
    held = ctx.sizes.get("n_routed_experts")
    if "expert_tokens" not in after or not held:
        return None
    waves = after["waves"] - before["waves"]
    layers = ctx.sizes["n_layers"] - ctx.sizes.get("first_k_dense_replace", 0)
    if waves <= 0 or layers <= 0:
        return None
    tokens = after["expert_tokens"] - before["expert_tokens"]
    return tokens / (waves * layers * held)
