"""GreenCache prefix reuse: prompt tokens spliced from the prefix cache
(the program's ``Request.prefix_reused``) over the prompt tokens of the
requests admitted in the window to engines that have a prefix cache, in %."""


def read(run):
    lo, hi = run.window
    mine = [r for r in run.requests
            if r.request is not None and lo <= r.request.submit_s < hi
            and r.model in run.prefix_models]
    total = sum(len(r.prompt) for r in mine)
    return 100.0 * sum(r.request.prefix_reused for r in mine) / total \
        if total else None
