"""Mean LSH candidate count (``SearchResult.n_candidates``) of the
answered requests."""


def read(rec):
    c = rec.stats.get("candidates")
    return sum(c) / len(c) if c else None
