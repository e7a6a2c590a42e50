"""Share of prompt tokens the prefix cache served (program counter)."""


def read(ctx):
    counters = ctx["counters"]
    total = counters.get("prompt_tokens", 0)
    if total <= 0 or "prefix_tokens" not in counters:
        return None
    return 100.0 * counters["prefix_tokens"] / total
