"""Work the scatter-add needs for one launch, whatever implements it.

Reads: ``n`` int32 ids and ``n x D`` f32 values.  Writes: the
``S x D`` f32 sums, and the instrumented kernel's counter, one int32
degree per 32-id commit group.  Operations: one add per value.
"""


def work(launch: dict) -> dict:
    n, d, s = launch["ids"], launch["width"], launch["segments"]
    return {
        "ops": float(n * d),
        "bytes": float(n * 4 + n * d * 4 + s * d * 4
                       + n // launch["commit_group"] * 4),
    }
