"""Output tokens produced inside the window, over the window."""
META = {"unit": "tokens/s", "source": "host_clock"}


def read(run):
    return run.data.get("serve_tokens_per_s")
