"""The work of a support-counting job, the same for every counting form.

A job tests ``C`` candidate itemsets for containment in each of ``T``
transactions and writes one count per candidate.  Whatever form does it
(popcount over packed rows, bit-plane or membership products, vertical
item-column ANDs), it must read, for every transaction, the bit of every
item that some candidate names, and write the ``C`` counts as 32-bit
integers.  Those compulsory bytes over the HBM bandwidth are the least time
of the job; no form can take less, so a share of it cannot pass 1.

The operations are not priced: the forms test containment on different
units (the vector unit's 32-bit words, the matrix unit's int8 products), so
a count of operations is either form-specific or, priced at the int8 matrix
peak, can be beaten by a form that does less arithmetic (the vertical forms
AND only the k columns a candidate names).  The byte bound holds for all.

The items a job touches are not reported by the program.  The items of the
frequent itemsets the job found are a subset of them, so counting those
gives a lower bound on the bytes, and the share stays a lower bound too.
"""

from __future__ import annotations


def count_bytes(n_candidates: int, n_txns: int, n_items_touched: int) -> float:
    """Compulsory HBM bytes of one counting job."""
    return n_txns * n_items_touched / 8.0 + 4.0 * n_candidates


def least_seconds(n_candidates: int, n_txns: int, n_items_touched: int,
                  hbm_bytes_per_s: float, chips: int = 1) -> float:
    """The least time of one job on ``chips`` chips at their peak bandwidth."""
    return count_bytes(n_candidates, n_txns, n_items_touched) / (
        hbm_bytes_per_s * chips)
