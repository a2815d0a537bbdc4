"""The device verifier's boot, from its ``warm in`` line."""

from ..reduce import Run


def warmup_s(run: Run):
    return run.log.warm[0] if run.log.warm else None


def cache_hits(run: Run):
    """Share of the warmed programs that the compile cache held."""
    if not run.log.warm:
        return None
    shapes = run.log.warm[1].get("warm", {}).values()
    hits = sum(s.get("cache_hits", 0) for s in shapes)
    misses = sum(s.get("cache_misses", 0) for s in shapes)
    return 100.0 * hits / (hits + misses) if hits + misses else None
