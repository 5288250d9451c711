"""Seconds of the live reshards over the seconds from each revocation to
the first step done on the new mesh (%). Moves resume_s."""


def read(trace, counts, cell):
    if not counts.get("resume_s"):
        return None
    return 100.0 * sum(counts["reshard_s"]) / sum(counts["resume_s"])
