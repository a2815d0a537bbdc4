"""How often a boot loaded its wave programs from the executable store
(``hotstuff_tpu/tpu/exe_store.py``) instead of building them, from the
``exe`` key that each pad shape's entry in the ``warm`` dict of the
``Device verifier ... warm in`` line carries.  A line from a program
without the store has no such key and gives None, as does a boot with
no line."""

from ..reduce import Run


def exe_load_share(run: Run):
    """Warmed shapes whose program was loaded, over warmed shapes, %."""
    if not run.log.warm:
        return None
    kinds = [s.get("exe") for s in run.log.warm[1].get("warm", {}).values()]
    if not kinds or None in kinds:
        return None
    return 100.0 * kinds.count("loaded") / len(kinds)
