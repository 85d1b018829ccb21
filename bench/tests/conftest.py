"""The tiny size of an MLP cell whose clients would be left without one
full local batch each.

``families/mlp.py``'s ``shrink`` cuts the cohort to 400 admissions and
the local batch to 16 but keeps the configuration's clients.  At the
cross-device configuration's 100 clients that deals each client 2
training rows: no batch, no step, uploads of zeros that no check can
read.  Every test here that takes ``tiny.cell`` of such a cell, without
naming the clients itself, gets it at 12 clients with batches of 4: 20
rows and 5 steps an epoch a client, and at half sampled 6 clients in 8
slots, so the partial participation and the padded slots stay.
"""
import pytest

from bench.tests import tiny

CROSS_DEVICE = {"clients": 12, "local_batch_size": 4}


def _starved(cell) -> bool:
    """True where a client's shard holds less than one local batch."""
    c = cell.config
    rows = int(c["cohort"]["split"][0] * c["cohort"]["admissions"])
    return rows // c["clients"] < c["local_batch_size"]


@pytest.fixture(autouse=True)
def _a_batch_per_client(monkeypatch):
    shrink = tiny.shrink

    def fitted(c, **over):
        small = shrink(c, **over)
        if c.config["family"] == "mlp" and "clients" not in over \
                and _starved(small):
            small = shrink(c, **{**CROSS_DEVICE, **over})
        return small

    monkeypatch.setattr(tiny, "shrink", fitted)
