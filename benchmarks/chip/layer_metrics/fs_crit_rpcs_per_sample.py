"""Critical-path RPCs of the verb a sample read sends (READ; its CLOSE is
asynchronous) per sample read, from the change in the Trainer's
`agent.stats.by_type` and the pipeline's sample count over the window.
Directory lookups are left out: checkpoint saves send them too."""


def read(ctx, device):
    if not ctx.get("samples"):
        return None
    return ctx["reads"] / ctx["samples"]
