"""Share of the prompt tokens admitted in the window that the prefix cache
served: ``PrefixCache.stats["hit_tokens"]`` over the prompt tokens of the
admissions, both counted per step over the window's steps."""


def read(ctx):
    if not ctx.cell["session"].get("prefix_cache_pages"):
        return None
    prompt = sum(s["admitted_prompt"] for s in ctx.steps)
    if prompt == 0:
        return None
    return 100.0 * sum(s["hit_tokens"] for s in ctx.steps) / prompt
