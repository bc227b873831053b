"""The warm-up plan reaches every shape the traffic can produce."""
import pytest

import run


def _bucket(p, max_len):
    return min(run._pow2(p), max_len)


@pytest.mark.parametrize("lo,hi,slots,max_len", [
    (128, 448, 8, 1024), (32, 512, 8, 1024), (32, 512, 16, 1024),
    (8, 40, 4, 128)])
def test_prefill_groups_cover_every_bucket_pair_and_group_size(
        lo, hi, slots, max_len):
    groups = run.prefill_groups(lo, hi, slots, max_len)
    need = {(_bucket(p, max_len), run._pow2(t))
            for k in range(1, slots + 1) for p in range(lo, hi + 1)
            for t in range(p + (k - 1) * lo, k * p + 1)}
    got = {(_bucket(max(g), max_len), run._pow2(sum(g))) for g in groups}
    assert got == need
    assert {len(g) for g in groups} == set(range(1, slots + 1))
    assert all(lo <= p <= hi for g in groups for p in g)


def test_closed_loop_sees_full_batches_only():
    closed = {"loop": "closed", "clients_per_slot": 2}
    assert run.active_counts(closed, 8) == [8]
    assert run.active_counts({"loop": "open"}, 3) == [1, 2, 3]
