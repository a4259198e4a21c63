"""Feature / label / mask IO — the GNNDatum equivalent.

Reference: core/ntsDataloador.hpp:29-305. File formats (readFeature_Label_Mask,
:156-303): feature file lines are ``ID f0 f1 ... f_{d-1}``; label file lines
``ID label``; mask file lines ``ID train|val|eval|test`` with train=0,
val/eval=1, test=2. ``random_generate`` (:63) fills ones-features, random
labels, and mask = id % 3 when files are absent.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

import numpy as np

from neutronstarlite_tpu.utils.logging import get_logger

log = get_logger("dataset")

MASK_TRAIN = 0
MASK_VAL = 1
MASK_TEST = 2

_MASK_NAMES = {"train": MASK_TRAIN, "val": MASK_VAL, "eval": MASK_VAL, "test": MASK_TEST}


@dataclasses.dataclass
class GNNDatum:
    """Per-vertex features, labels, masks for the full graph (host NumPy)."""

    feature: np.ndarray  # [V, f0] float32
    label: np.ndarray  # [V] int32
    mask: np.ndarray  # [V] int32 in {0=train, 1=val, 2=test}

    @property
    def v_num(self) -> int:
        return self.feature.shape[0]

    @property
    def feature_size(self) -> int:
        return self.feature.shape[1]

    @staticmethod
    def random_generate(
        v_num: int, feature_size: int, label_num: int, seed: int = 0
    ) -> "GNNDatum":
        """Deterministic stand-in data (reference: random_generate, :63-76
        uses ones-features, rand labels, mask = i % 3)."""
        rng = np.random.default_rng(seed)
        feature = rng.standard_normal((v_num, feature_size), dtype=np.float32) * 0.1
        label = rng.integers(0, label_num, size=v_num, dtype=np.int32)
        mask = (np.arange(v_num) % 3).astype(np.int32)
        return GNNDatum(feature=feature, label=label, mask=mask)

    @staticmethod
    def read_feature_label_mask(
        feature_file: str,
        label_file: str,
        mask_file: str,
        v_num: int,
        feature_size: int,
        seed: int = 0,
    ) -> "GNNDatum":
        """Load the three text files; any missing file falls back to the
        random_generate fill for that field (the reference prints "open ...
        fail!" and returns; we degrade per-field instead so real labels can be
        paired with generated features when a dataset ships without features)."""
        rng = np.random.default_rng(seed)

        def fallback(kind: str, path: str):
            # loud, because a typo'd path otherwise trains on fake data and
            # the only symptom is a quietly wrong accuracy (the reference
            # prints "open ... fail!", GNNDatum::readF*, ntsDataloador.hpp)
            if path:
                log.warning(
                    "%s file %r missing — generating random %s", kind, path, kind
                )

        if feature_file and os.path.exists(feature_file):
            feature = _read_feature_table(feature_file, v_num, feature_size)
        else:
            fallback("feature", feature_file)
            feature = rng.standard_normal((v_num, feature_size), dtype=np.float32) * 0.1

        if label_file and os.path.exists(label_file):
            label = _read_id_value_table(label_file, v_num).astype(np.int32)
        else:
            fallback("label", label_file)
            label = rng.integers(0, 2, size=v_num, dtype=np.int32)

        if mask_file and os.path.exists(mask_file):
            mask = _read_mask_table(mask_file, v_num)
        else:
            fallback("mask", mask_file)
            mask = (np.arange(v_num) % 3).astype(np.int32)

        return GNNDatum(feature=feature, label=label, mask=mask)

    @staticmethod
    def read_feature_label_mask_ogb(
        feature_file: str,
        label_file: str,
        mask_dir: str,
        v_num: int,
        feature_size: int,
        seed: int = 0,
    ) -> "GNNDatum":
        """OGB-converted layout (readFeature_Label_Mask_OGB,
        core/ntsDataloador.hpp:223-303): the feature file is one
        comma-separated line of ``feature_size`` floats per vertex (row i =
        vertex i, no ID column), the label file one bare integer per
        vertex, and ``mask_dir`` a DIRECTORY holding train.csv / valid.csv
        / test.csv, each listing member vertex ids. Vertices in none of the
        three lists get mask 3 (excluded from every split — the
        reference's "unknown" value). Missing files degrade per-field with
        the same loud fallback as the standard reader."""
        rng = np.random.default_rng(seed)

        if feature_file and os.path.exists(feature_file):
            feature = np.loadtxt(
                feature_file, dtype=np.float32, delimiter=",", ndmin=2
            )
            if feature.shape != (v_num, feature_size):
                raise ValueError(
                    f"{feature_file}: expected {(v_num, feature_size)}, "
                    f"got {feature.shape}"
                )
        else:
            if feature_file:
                log.warning(
                    "feature file %r missing — generating random features",
                    feature_file,
                )
            feature = (
                rng.standard_normal((v_num, feature_size), dtype=np.float32) * 0.1
            )

        if label_file and os.path.exists(label_file):
            label = np.loadtxt(label_file, dtype=np.int64).reshape(-1)
            if label.shape[0] != v_num:
                raise ValueError(
                    f"{label_file}: expected {v_num} labels, got {label.shape[0]}"
                )
            label = label.astype(np.int32)
        else:
            if label_file:
                log.warning(
                    "label file %r missing — generating random labels", label_file
                )
            label = rng.integers(0, 2, size=v_num, dtype=np.int32)

        mask = np.full(v_num, 3, dtype=np.int32)  # 3 = in no split
        names = (("train.csv", MASK_TRAIN), ("valid.csv", MASK_VAL),
                 ("test.csv", MASK_TEST))
        if mask_dir and os.path.isdir(mask_dir):
            for name, val in names:
                p = os.path.join(mask_dir, name)
                if not os.path.exists(p):
                    log.warning("mask split %r missing — split left empty", p)
                    continue
                ids = np.loadtxt(p, dtype=np.int64, delimiter=",", ndmin=1)
                mask[ids.reshape(-1)] = val
        else:
            if mask_dir:
                log.warning(
                    "mask dir %r missing — falling back to mask = id %% 3",
                    mask_dir,
                )
            mask = (np.arange(v_num) % 3).astype(np.int32)

        return GNNDatum(feature=feature, label=label, mask=mask)

    def label_num(self) -> int:
        return int(self.label.max()) + 1

    def mask_tensor(self, which: int) -> np.ndarray:
        return (self.mask == which).astype(np.float32)


def _read_feature_table(path: str, v_num: int, feature_size: int) -> np.ndarray:
    if path.endswith(".npy"):
        # binary fast path for large feature tables (Reddit-scale text
        # tables are >1 GB; prep.py emits .npy for them)
        out = np.load(path).astype(np.float32, copy=False)
        if out.shape != (v_num, feature_size):
            raise ValueError(
                f"{path}: expected shape {(v_num, feature_size)}, got {out.shape}"
            )
        return out
    data = np.loadtxt(path, dtype=np.float32)
    if data.ndim == 1:
        data = data.reshape(1, -1)
    if data.shape[1] != feature_size + 1:
        raise ValueError(
            f"{path}: expected {feature_size + 1} columns (ID + features), got {data.shape[1]}"
        )
    out = np.zeros((v_num, feature_size), dtype=np.float32)
    ids = data[:, 0].astype(np.int64)
    out[ids] = data[:, 1:]
    return out


def _read_id_value_table(path: str, v_num: int) -> np.ndarray:
    data = np.loadtxt(path, dtype=np.int64)
    if data.ndim == 1:
        data = data.reshape(1, -1)
    out = np.zeros(v_num, dtype=np.int64)
    out[data[:, 0]] = data[:, 1]
    return out


def _read_mask_table(path: str, v_num: int) -> np.ndarray:
    out = np.full(v_num, MASK_TEST, dtype=np.int32)
    with open(path) as fh:
        for line in fh:
            parts = line.split()
            if len(parts) < 2:
                continue
            out[int(parts[0])] = _MASK_NAMES.get(parts[1].strip().lower(), MASK_TEST)
    return out


@dataclasses.dataclass
class TokenDatum:
    """An integer datum over an implicit graph: ``tokens`` [sequences,
    length] int32, ids inside the vocabulary slice ``0 .. vocab`` this chip
    holds. The vertices are the positions; vertex ``i`` of a sequence has an
    in-edge from every ``j <= i`` of that sequence, and no table holds them
    (ops/causal_attention.py enumerates them). An id outside the slice is an
    error, never clamped: a clamp would train on another corpus."""

    tokens: np.ndarray
    vocab: int

    def __post_init__(self) -> None:
        tokens = np.asarray(self.tokens)
        if tokens.ndim != 2 or not np.issubdtype(tokens.dtype, np.integer):
            raise ValueError(
                f"a token datum is an integer array [sequences, length], got "
                f"{tokens.dtype} {tokens.shape}"
            )
        lo, hi = (int(tokens.min()), int(tokens.max())) if tokens.size else (0, 0)
        if lo < 0 or hi >= self.vocab:
            raise ValueError(
                f"token ids {lo} .. {hi} leave the vocabulary slice 0 .. "
                f"{self.vocab - 1} this chip holds"
            )
        self.tokens = tokens.astype(np.int32)

    @property
    def sequences(self) -> int:
        return self.tokens.shape[0]

    @property
    def length(self) -> int:
        return self.tokens.shape[1]

    @staticmethod
    def random_generate(sequences: int, length: int, vocab: int, seed: int = 0) -> "TokenDatum":
        """Ids uniform over the slice, from the seed."""
        rng = np.random.default_rng(seed)
        return TokenDatum(rng.integers(0, vocab, size=(sequences, length), dtype=np.int32), vocab)

    @staticmethod
    def read(path: str, length: int, vocab: int) -> "TokenDatum":
        """A ``.npy`` file of ids, [sequences, length] or flat (then cut
        into whole sequences of ``length``; a remainder is dropped)."""
        tokens = np.load(path)
        if tokens.ndim == 1:
            tokens = tokens[: tokens.shape[0] // length * length].reshape(-1, length)
        if tokens.shape[1] != length:
            raise ValueError(
                f"{path} holds sequences of {tokens.shape[1]} tokens, SEQ_LENGTH is {length}"
            )
        return TokenDatum(tokens, vocab)
