"""The ``quant_aggregate`` kernel's share of its roofline, in %: the least
time its calls could take at the chip's HBM bandwidth (or peak FLOP rate,
whichever bounds), over the device time of its calls in the traced window.
A call's time includes the ops XLA put before it to stage its operands in
VMEM (bench/trace.py), since those do its reads from HBM. The clients per
call are the cell's slots (sync) or its buffer (FedBuff); the values per
client, the family's parameter leaves padded to whole blocks."""
from bench import costs, families

KERNEL = "quant_aggregate"


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    n_calls, seconds = tr["kernels"].get(KERNEL, (0, 0.0))
    if not n_calls or seconds <= 0:
        return None
    tp = ctx["cell"]["traffic"]["train_params"]
    clients = int(tp.get("async_buffer") if tp.get("mode") == "async"
                  else tp.get("max_cohort") or tp.get("cohort"))
    conf = ctx["cell"]["config"]
    cost = costs.quant_aggregate_cost(
        clients, costs.packed_size(families.of(conf).leaf_sizes(conf)))
    peaks = ctx["peaks"]
    least = max(cost["bytes"] / peaks["hbm_bytes_per_s"],
                cost["flops"] / peaks["bf16_flops_per_s"])
    return 100.0 * n_calls * least / seconds
