"""Model FLOPs per token (forward + 2x backward, causal; no MGRIT extra
sweeps, no recomputation) times the window's tokens per second, over the
cell's chips times the bf16 peak."""
META = {"layer": "step / solver", "unit": "%", "source": "host_clock",
        "moves": "train_tokens_per_s"}


def read(run):
    d = run.data
    if "train_tokens_per_s" not in d:
        return None
    return (100.0 * d["flops_per_token"] * d["train_tokens_per_s"]
            / (run.chips * run.peak["bf16_flops_per_s"]))
