"""Data-fragmentation invariants (paper §III-A) — property-based."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.partitioner import fragmented_overlap, partition
from repro.data.synthetic import generate, make_task


@given(n=st.integers(30, 300), n_clients=st.integers(1, 8),
       fp=st.floats(0, 1), ff=st.floats(0, 1), seed=st.integers(0, 99))
@settings(max_examples=40, deadline=None)
def test_partition_invariants(n, n_clients, fp, ff, seed):
    # normalize fractions to a simplex point
    rest = max(1e-9, fp + ff)
    if rest > 1:
        fp, ff = fp / rest, ff / rest
    fpart = 1.0 - fp - ff
    spec = make_task("smnist")
    data = generate(spec, n, seed=seed)
    clients = partition(data, n_clients, frac_paired=fp, frac_fragmented=ff,
                        frac_partial=fpart, seed=seed)
    assert len(clients) == n_clients

    # 1. paired rows align within a client
    for c in clients:
        np.testing.assert_array_equal(c.paired_a.ids, c.paired_b.ids)

    # 2. conservation: every sample id appears exactly once per modality it has
    ids_a = np.concatenate([np.concatenate([c.partial_a.ids, c.frag_a.ids,
                                            c.paired_a.ids]) for c in clients])
    ids_b = np.concatenate([np.concatenate([c.partial_b.ids, c.frag_b.ids,
                                            c.paired_b.ids]) for c in clients])
    assert len(ids_a) == len(set(ids_a))  # no duplicates within a modality
    assert len(ids_b) == len(set(ids_b))
    all_ids = set(ids_a) | set(ids_b)
    assert all_ids == set(data.ids)  # every sample placed somewhere

    # 3. partial samples exist in exactly one modality anywhere
    part_ids = set()
    for c in clients:
        part_ids |= set(c.partial_a.ids) | set(c.partial_b.ids)
    both = set(ids_a) & set(ids_b)
    assert not (part_ids & both)

    # 4. fragmented rows: A-side and B-side live on DIFFERENT clients
    if n_clients > 1:
        for k, c in enumerate(clients):
            for other in clients[:k] + clients[k + 1:]:
                pass  # ownership split is checked via overlap below
        ov = fragmented_overlap(clients)
        for c in clients:
            # no client holds both halves of the same fragmented sample
            assert not (set(c.frag_a.ids) & set(c.frag_b.ids))
        # every fragmented id with both halves somewhere is in the overlap
        fa = set().union(*[set(c.frag_a.ids) for c in clients])
        fb = set().union(*[set(c.frag_b.ids) for c in clients])
        assert set(ov) == (fa & fb)

    # 5. features/labels travel with their ids
    for c in clients:
        for view in (c.partial_a, c.frag_a, c.paired_a):
            for row, gid in enumerate(view.ids):
                src = np.where(data.ids == gid)[0][0]
                np.testing.assert_array_equal(view.x[row], data.x_a[src])
                np.testing.assert_array_equal(view.y[row], data.y[src])


def test_single_client_fragmented_degenerates_to_self():
    spec = make_task("smnist")
    data = generate(spec, 50, seed=1)
    clients = partition(data, 1, frac_paired=0.2, frac_fragmented=0.6,
                        frac_partial=0.2, seed=1)
    # with one client, "fragmented" rows live on the same client by force
    assert len(clients) == 1
