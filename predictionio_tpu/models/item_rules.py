"""Business rules over a catalog of items, the part the item-scoring
templates share: which items a query may be answered with
(isCandidateItem of scala-parallel-similarproduct ALSAlgorithm.scala
:233+ and scala-parallel-ecommercerecommendation ALSAlgorithm.scala),
in the two forms the serving layouts read it in.

- The host layouts build one boolean vector a query
  (:func:`candidate_mask`) from per-category vectors
  (:func:`build_category_masks`), or read a query's category vector off
  the rule words (:func:`category_mask_of_words`: models/similarproduct,
  whose model carries its categories as words alone).
- The device layouts keep every item's categories resident as bits
  (:func:`category_words`) beside an eligibility array, and a flush
  sends a row of wanted bits and a short list of excluded indices a
  query (:class:`RuleDevice`), the arguments of ops/topk.py
  ``_rules_and_select``.

models/ecommerce and models/similarproduct both import from here, and
neither imports the other.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Iterable, Optional, Tuple

import numpy as np

from predictionio_tpu.ops import topk

#: category name -> (word of the item's rule words, bit mask in it)
CategoryBits = Dict[str, Tuple[int, "np.uint32"]]


def build_category_masks(items: Dict[int, Any],
                         n_items: int) -> Dict[str, np.ndarray]:
    """category -> (n_items,) bool, from ``items`` (index -> an object
    with ``categories``: a tuple of names or None)."""
    masks: Dict[str, np.ndarray] = {}
    for ix, item in items.items():
        for cat in item.categories or ():
            masks.setdefault(cat, np.zeros(n_items, dtype=bool))[ix] = True
    return masks


def candidate_mask(n_items: int,
                   trained: np.ndarray,
                   category_masks: Dict[str, np.ndarray],
                   categories,
                   white: Optional[set],
                   black: set,
                   exclude: set) -> np.ndarray:
    """isCandidateItem as one boolean vector (ALSAlgorithm.scala:233+).

    Inputs are host numpy after train/load (deploy no longer device_puts
    pushes every numeric leaf); the mask is host-side scratch, so coerce.
    """
    mask = np.array(trained, dtype=bool)
    if categories is not None:
        cat_mask = np.zeros(n_items, dtype=bool)
        for c in categories:
            m = category_masks.get(c)
            if m is not None:
                cat_mask |= np.asarray(m)
        mask &= cat_mask
    if white is not None:
        white_mask = np.zeros(n_items, dtype=bool)
        white_mask[sorted(white)] = True
        mask &= white_mask
    for ix in black | exclude:
        mask[ix] = False
    return mask


def category_words(items: Dict[int, Any], n_items: int,
                   category_masks: Optional[Dict[str, np.ndarray]] = None
                   ) -> Tuple[CategoryBits, np.ndarray]:
    """Every item's categories as bits, for the device programs'
    `mask` stage: -> (category -> (word, bit mask), (w, n_items)
    uint32). Bit 0 of word 0 is set on every item (topk.RULE_ANY_BIT:
    what a query with no categories asks for, so that an item with no
    category still answers it); category j, in name order, is bit
    j + 1. ``category_masks`` (:func:`build_category_masks` of the same
    items) is used where the model carries it."""
    if category_masks is None:
        category_masks = build_category_masks(items, n_items)
    names = sorted(category_masks)
    words = np.zeros((-(-(len(names) + 1) // 32), n_items), np.uint32)
    words[0] = topk.RULE_ANY_BIT
    bits: CategoryBits = {}
    for j, name in enumerate(names):
        word, bit = divmod(j + 1, 32)
        bits[name] = (word, np.uint32(1 << bit))
        words[word] |= np.where(np.asarray(category_masks[name]),
                                bits[name][1], np.uint32(0))
    return bits, words


def want_bits(bits: CategoryBits, n_words: int,
              categories: Optional[Iterable[str]]) -> np.ndarray:
    """A query's row of wanted bits, (n_words,) uint32: every bit for a
    query with no categories; else its categories' bits (a name no item
    has asks for nothing, and so does an empty list)."""
    if categories is None:
        return np.full(n_words, 0xFFFFFFFF, np.uint32)
    want = np.zeros(n_words, np.uint32)
    for name in categories:
        word, bit = bits.get(name, (0, 0))
        want[word] |= bit
    return want


def category_mask_of_words(bits: CategoryBits, words: np.ndarray,
                           categories: Iterable[str]) -> np.ndarray:
    """(n_items,) bool: the items in any of ``categories``, read off
    the rule words: what :func:`candidate_mask` makes of
    :func:`build_category_masks`' vectors, one vector a query and none
    a category held."""
    want = want_bits(bits, words.shape[0], categories)
    return ((words & want[:, None]) != 0).any(axis=0)


@dataclass
class RuleDevice:
    """What a device layout with rules keeps resident beside its
    factors, and the rule half of a flush's arguments: the item factors
    the scores are taken against, every item's category bits, and the
    eligibility array (trained, and whatever else the engine rules out
    for every query alike)."""
    item_factors: Any            # (n_items, r) float32, device
    rule_words: Any              # (w, n_items) uint32, device
    category_bits: CategoryBits
    eligible: Any                # (n_items,) bool, device

    @property
    def n_items(self) -> int:
        return int(self.item_factors.shape[0])

    @property
    def n_words(self) -> int:
        return int(self.rule_words.shape[0])

    def rule_arrays(self) -> Tuple[Any, ...]:
        """The resident arrays, for ``nbytes``."""
        return (self.item_factors, self.rule_words, self.eligible)

    def nbytes(self) -> int:
        return int(sum(a.nbytes for a in self.rule_arrays()))

    def rule_arguments(self, bucket: int, longest: int):
        """topk.blank_rule_arguments at this layout's shapes: (bucket,
        w) wanted bits, every one set, and (bucket, E) exclusions, every
        one padding, E the declared width that holds ``longest``."""
        return topk.blank_rule_arguments(bucket, self.n_words, longest,
                                         self.n_items)

    def fill_rule_row(self, want, exclude, r: int, categories,
                      gone) -> None:
        """Row r of a flush's rule arguments: the query's categories
        and the item indices it must not be answered with."""
        if categories is not None:
            want[r] = want_bits(self.category_bits, self.n_words,
                                categories)
        exclude[r, :len(gone)] = list(gone)
