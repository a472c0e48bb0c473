"""Bytes the page pool moved (``pool:<name>`` bank ``bytes_moved``: each
page store, load, eviction, restore and defrag move, at the page's logical
bytes) per generated token in the traced window (page pool).  None where no
pool counts bytes."""


def read(run):
    counted = [counts["bytes_moved"] for domain, counts in run.banks.items()
               if domain.startswith("pool:") and "bytes_moved" in counts]
    tokens = run.facts.get("generated_tokens")
    if not counted or not tokens:
        return None
    return sum(counted) / tokens
