"""Work the histogram needs for one launch, whatever implements it.

Reads: one byte per 8-bit channel value (the paper's count).  Writes: the
``channels x bins`` int32 counts, and the instrumented kernel's counter,
one int32 degree per 32-value commit group.  Operations: one increment
per channel value.
"""


def work(launch: dict) -> dict:
    values = launch["pixels"] * launch["channels"]
    return {
        "ops": float(values),
        "bytes": float(values * 1 + launch["channels"] * launch["num_bins"] * 4
                       + values // launch["commit_group"] * 4),
    }
