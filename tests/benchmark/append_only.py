"""The benchmark grows by appending: the rule, stated once.

A cell (or a set of metrics) that came as files and entries pins its place
in ``BENCHMARK.json``: the entries that stood before it and its own, each
as ``(name, digest)``.  :func:`problems` says how a loaded manifest breaks
the rule for such a pin:

- in each of ``LISTS`` the entries that stood before come first, in their
  order, then the pin's own, in theirs; whatever later cells append
  follows them and is none of this pin's business;
- a pinned entry keeps its content.  The one change it may take is cells
  appended at the end of its ``workloads``: that is how a later cell joins
  a metric that is there.

So a new entry put ahead of an old one, two old entries swapped, or an old
entry edited (a bound, a unit, a cell taken out of its ``workloads``) is
refused, and a sixth cell appended after everything is not.

The next cell's test pins itself the same way.  Its pin is printed by

    python tests/benchmark/append_only.py NAME [NAME ...]

with the names of the cell's own entries (its configuration, its cell,
its metrics): the entries not named are what stood before.
"""

import hashlib
import json
import os
import sys

LISTS = ("configs", "workloads", "end_to_end", "per_layer")


def digest(entry) -> str:
    """Twelve hex digits of the entry's content, key order aside."""
    text = json.dumps(entry, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(text.encode("utf-8")).hexdigest()[:12]


def same(entry, want) -> bool:
    """``entry`` is the one pinned as ``want``, or that one with cells
    appended to its ``workloads``."""
    cells = entry.get("workloads")
    if not isinstance(cells, list):
        return digest(entry) == want
    return any(digest(dict(entry, workloads=cells[:k])) == want
               for k in range(1, len(cells) + 1))


def pin(bench, names=()):
    """``(before, own)`` of the manifest as it stands: for each list, the
    ``(name, digest)`` of the entries not in ``names``, then of those
    in it."""
    before, own = {}, {}
    for key in LISTS:
        rows = [(e["name"], digest(e)) for e in bench[key]]
        before[key] = tuple(r for r in rows if r[0] not in names)
        own[key] = tuple(r for r in rows if r[0] in names)
    return before, own


def problems(bench, before, own) -> list[str]:
    """Every breach of the rule for the pin ``(before, own)``; empty when
    ``bench`` keeps it."""
    out = []
    for key in LISTS:
        pinned = tuple(before.get(key, ())) + tuple(own.get(key, ()))
        entries = bench[key]
        names = [e.get("name") for e in entries]
        want = [name for name, _ in pinned]
        if names[:len(want)] != want:
            out.append(f"{key}: {want} must open the list in this order, "
                       f"and it opens with {names[:len(want)]}")
            continue
        for entry, (name, d) in zip(entries, pinned):
            if not same(entry, d):
                out.append(f"{key}: {name} was edited")
    return out


def _print(label, part):
    print(f"{label} = {{")
    for key in LISTS:
        print(f'    "{key}": (')
        for row in part[key]:
            print(f"        {row!r},")
        print("    ),")
    print("}")


if __name__ == "__main__":
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        before, own = pin(json.load(f), set(sys.argv[1:]))
    _print("BEFORE", before)
    _print("OWN", own)
