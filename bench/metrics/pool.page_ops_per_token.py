"""Page movements (``pool:<name>`` bank ``movements``) per generated token
in the traced window (page pool)."""


def read(run):
    moves = sum(counts.get("movements", 0) for domain, counts in run.banks.items()
                if domain.startswith("pool:"))
    tokens = run.facts.get("generated_tokens")
    if not tokens:
        return None
    return moves / tokens
