"""Mean over the window's saves of the time from the ``save`` call to its
commit, in seconds, as ``save_commit_s`` takes it: per layer in a cell
whose fsync'd writes follow the machine's disk too closely to hold it end
to end. It moves ``train_tokens_per_s`` there once a save's drain outlasts
the save interval, so that the next capture waits for host buffers."""


def read(ctx):
    done = [s for s in ctx.saves if "t_committed" in s]
    if not done:
        return None
    return sum(s["t_committed"] - s["t_call"] for s in done) / len(done)
