"""train_mfu: the local training's required operations over what the
chips' bf16 peak could do in the traced job.

Required operations are the matrix products of SGD on the MLP, for
real clients in real rounds only (padded slots and rounds do not
count): per example, the forward pass (2 a b for each a x b weight),
the weight gradients (2 a b each) and the activation gradients of
every layer but the first (2 a b each; nothing needs the gradient of
the input).  Biases, activations and the loss are left out.  Examples
are the full batches of each local epoch.  Pruned rounds count the
effective (kept) widths.
"""
LAYER = "device"
UNIT = "%"
BETTER = "higher"
SOURCE = "device_trace"
MOVES = "rounds_per_s"


def example_flops(features) -> int:
    """Matrix-product operations of one SGD example through the MLP."""
    dims = list(zip(features[:-1], features[1:]))
    mm = [2 * a * b for a, b in dims]
    return 2 * sum(mm) + sum(mm[1:])


def job_flops(config: dict, records) -> int:
    """Required training operations of the rounds in ``records``."""
    c = config
    n_train = int(c["cohort"]["split"][0] * c["cohort"]["admissions"])
    per_client = n_train // c["clients"]
    examples = (per_client // c["local_batch_size"]) \
        * c["local_batch_size"] * c["local_epochs"]
    total = 0
    for r in records:
        hidden = list(r.hidden_sizes) or list(c["features"][1:-1])
        feats = [c["features"][0]] + hidden + [c["features"][-1]]
        total += r.num_participants * examples * example_flops(feats)
    return total


def read(ctx):
    if not ctx["planes"] or ctx["window_s"] <= 0:
        return None
    peak = ctx["peak"]["bf16_flops_per_s"] * ctx["chips"]
    return 100.0 * job_flops(ctx["cell"].config, ctx["records"]) \
        / (ctx["window_s"] * peak)
