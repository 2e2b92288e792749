"""Share of its roofline that the Pallas paged-attention kernel reaches:
for every layer of every serve-step call in the profiled segment, the
least time for its required operations and bytes (the live tokens' K and
V, q and the output; from the calls' per-slot lengths), over the summed
device time of the ops under the ``paged_attn_core_fused`` scope, which
holds the Pallas kernel's call and nothing else that runs on the device
(the page pools' reshapes around it are free)."""
from chipbench import flops

META = {"layer": "model / kernels", "unit": "%", "source": "device_trace",
        "moves": "tpot_p90_ms"}
SCOPE = "paged_attn_core_fused"


def read(run):
    t, d = run.trace_data, run.data
    if t is None or not d.get("calls"):
        return None
    spent = t.scope_s(SCOPE)
    if spent <= 0:
        return None
    L = d["model"]["n_layers"]
    need = sum(L * flops.roofline_s(*flops.paged_attn_call(
        d["model"], ln, nn), run.peak)[0] for ln, nn in d["calls"])
    return 100.0 * need / spent
