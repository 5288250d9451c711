"""Bytes the live reshards moved (from reshard_bytes_per_device), over the
host-clock seconds of reshard_tree up to block_until_ready (GB/s). Moves
resume_s."""


def read(trace, counts, cell):
    if not counts.get("reshard_s"):
        return None
    return sum(counts["reshard_bytes"]) / sum(counts["reshard_s"]) / 1e9  # repro-lint: disable=U002
