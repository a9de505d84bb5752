"""The comparison that decides ``correct``.

Every verdict a window request returned is compared with what the
plain reference expects for its item.  The configuration's reference
module (``perf/reference/<reference>.py``) answers two questions:
``known(cfg, traffic, item)`` gives every label of the item with the
verdict the generator knows for it, or None where it knows none, and
``search(cfg, traffic, item, labels, **options)`` decides the labels
asked for by its own search.  This module takes a known verdict as it
stands and has the search decide the rest.  On a sample of items drawn
from the seed (``audit``) it has the search decide every label and
counts each one that disagrees with a known verdict: that checks the
yardstick itself.  A verdict that is missing, extra, or not a boolean
counts as a mismatch.
"""

from __future__ import annotations

import random


class Expected:
    """Reference verdicts per pool item, computed once each.

    ``options`` go to the reference's ``search`` (the control's): with
    any, the search decides every label and nothing known is used or
    held against it."""

    def __init__(self, ref, cfg, traffic, **options):
        self.ref = ref
        self.cfg = cfg
        self.traffic = traffic
        self.options = options
        self.cache = {}
        self.reference_runs = 0     # labels the search decided
        self.disagreements = 0      # of them, those against a known one

    def of(self, item_id, item, audit=False):
        """{label: verdict} for one item, in the entry's labels."""
        if item_id in self.cache:
            return self.cache[item_id]
        known = self.ref.known(self.cfg, self.traffic, item)
        if audit or self.options:
            need = set(known)
        else:
            need = {label for label, v in known.items() if v is None}
        out = dict(known)
        if need:
            found = self.ref.search(self.cfg, self.traffic, item, need,
                                    **self.options)
            self.reference_runs += len(need)
            for label in need:
                out[label] = found[label]
                if not self.options and known[label] is not None \
                        and found[label] != known[label]:
                    self.disagreements += 1
        self.cache[item_id] = out
        return out


def compare(expected, items, answers, audit_ids):
    """Mismatched labels over all answered rows, the labels compared,
    and the first few mismatches as [item id, label, got, want].

    ``items`` maps item id -> item; ``answers`` is [(item id, {label:
    verdict})] for every row a window request returned or should have.
    """
    mismatches = compared = 0
    first = []
    for item_id, got in answers:
        want = expected.of(item_id, items[item_id],
                           audit=item_id in audit_ids)
        for label in set(want) | set(got):
            compared += 1
            g = got.get(label)
            if g not in (True, False) or g != want.get(label):
                mismatches += 1
                if len(first) < 5:
                    first.append([list(item_id), str(label), str(g),
                                  str(want.get(label))])
    return mismatches, compared, first


def audit_sample(seed, item_ids, n):
    """``n`` item ids drawn from the seed for the reference audit."""
    ids = sorted(set(item_ids))
    return set(random.Random(seed).sample(ids, min(n, len(ids))))
