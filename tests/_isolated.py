"""The reference of the serving tests: isolated generation."""


def isolated_tokens(pipe, reqs):
    """Each request's greedy tokens from ``pipe.generate`` (an
    ``AsymmetricPipeline``) run on that request alone."""
    return [list(pipe.generate(r.prompt[None], max_new=r.max_new_tokens)[0])
            for r in reqs]


def serve_isolated(pipe, reqs):
    """Stamp each request's ``output`` with its isolated tokens."""
    for r, toks in zip(reqs, isolated_tokens(pipe, reqs)):
        r.output = toks
    return reqs
