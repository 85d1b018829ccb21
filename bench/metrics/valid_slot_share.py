"""valid_slot_share: the share of the slots that the traced job's round
programs trained that held a sampled client: the program's per-round
records, participants over slots (``LoopRecord.slots``).  A padded slot
trains and is thrown away.  Records without ``slots`` give nothing."""
LAYER = "fused chunk program"
UNIT = "%"
BETTER = "higher"
SOURCE = "program_counter"
MOVES = "rounds_per_s"


def read(ctx):
    slots = [getattr(r, "slots", None) for r in ctx["records"]]
    if not slots or None in slots or not sum(slots):
        return None
    return 100.0 * sum(int(r.num_participants) for r in ctx["records"]) \
        / sum(int(s) for s in slots)
