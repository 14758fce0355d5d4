"""Data substrate: transaction datasets — synthetic generators, file I/O and
shard balancing."""

from .generator import ibm_generator, chess_like, mushroom_like, dataset_by_name
from .loader import load_transactions, save_transactions, dataset_stats

__all__ = [
    "ibm_generator", "chess_like", "mushroom_like", "dataset_by_name",
    "load_transactions", "save_transactions", "dataset_stats",
]
