"""Work DeepSeek-V3's router needs for one batch, whatever implements it.

Reads: ``T x H`` bf16 hidden states, the ``H x E`` float32 gate weight and
the ``E`` float32 correction bias.  Writes: ``T x k`` int32 expert ids and
``T x k`` float32 gates.  Operations: the logits' multiply-adds,
``2 T H E``; the sigmoid, group and top-k selection are ``O(T E)`` and
left out.
"""


def work(launch: dict) -> dict:
    t, h, e, k = (launch["tokens"], launch["hidden"], launch["segments"],
                  launch["top_k"])
    return {"ops": float(2 * t * h * e),
            "bytes": float(t * h * 2 + h * e * 4 + e * 4 + t * k * 8)}
