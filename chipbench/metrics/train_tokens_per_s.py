"""Tokens of all whole steps completed in the window, over the time from
window start to the end of the last of them; global over the cell's
chips."""
META = {"unit": "tokens/s", "source": "host_clock"}


def read(run):
    return run.data.get("train_tokens_per_s")
