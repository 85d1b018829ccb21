"""upload_bytes_per_round: wire bytes of every client upload of the
traced job (the program's per-round byte records, which the check holds
to the bytes of the payloads themselves), over its rounds."""
LAYER = "wire emission"
UNIT = "bytes/round"
BETTER = "lower"
SOURCE = "program_counter"
MOVES = "rounds_per_s"


def read(ctx):
    if not ctx["rounds"]:
        return None
    return sum(int(r.sparse_bytes) for r in ctx["records"]) / ctx["rounds"]
