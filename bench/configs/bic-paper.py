"""The BIC paper's record format: the plain reference beside
``bic-paper.json``.

Records are ``words_per_record`` words of ``word_bits`` bits, uniform from
the seed; the index has one key row per word value.  Imports nothing of
the program.
"""
from __future__ import annotations

import numpy as np

from bench import reference

#: the control's broken guarantee: the index built from all but the last
#: word of each record (a record only partly indexed)
CONTROL_WORDS_DROPPED = 1


def generate_pool(cfg, seed: int) -> np.ndarray:
    """The host pool of records, (pool_records, words_per_record) int32."""
    r = reference.rng(seed, "data")
    shape = (cfg["pool_records"], cfg["words_per_record"])
    raw = r.integers(0, 1 << cfg["word_bits"], shape, dtype=np.uint8)
    return raw.astype(np.int32)


def index_rows(cfg, records: np.ndarray) -> np.ndarray:
    """Key-major packed index rows of ``records``: (num_keys, N/32)."""
    return reference.index_rows(records, cfg["num_keys"])


def control_rows(cfg, records: np.ndarray) -> np.ndarray:
    """The control: the same rows with the last word of every record
    left out."""
    return reference.index_rows(records[:, :-CONTROL_WORDS_DROPPED],
                                cfg["num_keys"])
