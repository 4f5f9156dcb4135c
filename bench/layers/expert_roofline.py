"""expert_roofline: the held experts' grouped products' share of the
chip's bf16 peak, in percent: the products a round needs at the mean
load of a uniform router (the configuration module's
``expert_flops_round``: ``tokens x top_k x held / routed`` rows a layer,
forward and input gradient for training tokens, forward for evaluation
tokens) times the traced rounds, over the device time of the
grouped-product kernels (``expert_ms_per_round``) on the slowest chip
times the bf16 peak.  The backward recomputes the forward products
(recomputation is not counted), so a perfect kernel reads two thirds.

``ctx`` carries no configuration: the cell is the one among this
metric's ``workloads`` in ``BENCHMARK.json`` whose round FLOPs are the
context's."""
import os

import harness
import work
from layers_common import used_devices

NAME = os.path.splitext(os.path.basename(__file__))[0]


def _cell_flops(ctx):
    entry = {m["name"]: m for m in harness.benchmark()["per_layer"]}[NAME]
    for name in entry.get("workloads", []):
        cell = harness.find_cell(name)
        ref = cell["ref"]
        if not hasattr(ref, "expert_flops_round"):
            continue
        if work.round_flops(cell["cfg"], ref, cell["traffic"]) \
                == ctx["round_flops"]:
            return ref.expert_flops_round(cell["cfg"], cell["traffic"])
    return None


def read(tr, ctx):
    if ctx["rounds"] < 1:
        return None
    t = max((_experts.expert_ns(d)
             for d in used_devices(tr, ctx["chips"])), default=0)
    if not t:
        return None
    flops = _cell_flops(ctx)
    if flops is None:
        return None
    return 100.0 * ctx["rounds"] * flops / (t / 1e9
                                            * ctx["peak"]["bf16_flops"])


_experts = harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "expert_ms_per_round.py"), "bench_layer_expert_ms")
