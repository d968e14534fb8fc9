"""Share of prompt tokens served from cached prefix pages
(``RequestResult.prefix_hit_tokens`` over prompt lengths), in %."""


def read(ctx):
    prompt = sum(len(r.ask.prompt) for r in ctx["records"])
    if not prompt:
        return None
    return 100.0 * sum(r.hit for r in ctx["records"]) / prompt
