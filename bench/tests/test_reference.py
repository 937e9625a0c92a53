"""The plain reference: membership replayed in call order."""
import numpy as np

from bench import keys as K
from bench import reference


def _u8(cls, n):
    return np.full(n, cls, np.uint8)


def test_members_inserts_and_deletes_in_call_order():
    placed = np.array([True, False, True, True])
    ref = reference.Reference(placed)
    ref.insert(3, _u8(K.FRESH, 2), np.array([0, 1]), np.array([True, False]))
    ref.delete(5, _u8(K.MEMBER, 1), np.array([2]), np.array([True]))
    # before the insert call, at it, after it; member 2 before/after delete
    cls = np.array([K.FRESH, K.FRESH, K.FRESH, K.FRESH, K.MEMBER, K.MEMBER,
                    K.MEMBER, K.MEMBER, K.ABSENT], np.uint8)
    idx = np.array([0, 0, 0, 1, 2, 2, 1, 3, 0])
    call = np.array([2, 3, 4, 9, 5, 6, 0, 9, 9])
    assert ref.live(call, cls, idx).tolist() == [
        False, False, True, False, True, False, False, True, False]


def test_verdict_counts_false_negatives_and_positives():
    placed = np.ones(10, bool)
    ref = reference.Reference(placed)
    ref.lookup(0, _u8(K.MEMBER, 3), np.array([0, 1, 2]),
               np.array([True, False, True]))
    ref.lookup(1, _u8(K.ABSENT, 4), np.arange(4),
               np.array([False, True, False, False]))
    v = ref.verdict()
    assert v["false_negatives"] == 1
    assert v["false_positives"] == 1
    assert v["non_member_lookups"] == 4 and v["fpr"] == 0.25


def test_deletes_of_non_members_are_blind():
    ref = reference.Reference(np.array([True, False]))
    ref.delete(0, _u8(K.MEMBER, 2), np.array([0, 1]), np.array([False, True]))
    v = ref.verdict()
    assert v["delete_misses"] == 1 and v["blind_deletes"] == 1


def test_acked_and_deleted():
    ref = reference.Reference(np.zeros(0, bool))
    ref.insert(0, _u8(K.FRESH, 4), np.arange(4), np.array([1, 1, 0, 1], bool))
    ref.delete(1, _u8(K.FRESH, 1), np.array([3]), np.array([True]))
    assert ref.acked(K.FRESH).tolist() == [0, 1]
    assert ref.deleted(K.FRESH).tolist() == [3]
