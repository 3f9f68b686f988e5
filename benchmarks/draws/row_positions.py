"""``per_row`` distinct positions in every row, ascending, as flat indices
into [rows * row_len]: the masked positions of a masked-LM batch."""

import numpy as np


def draw(rng, field, resolve):
    rows, row_len, per = (resolve(field[k])
                          for k in ("rows", "row_len", "per_row"))
    pos = np.argsort(rng.random((rows, row_len)), axis=1)[:, :per]
    pos = np.sort(pos, axis=1) + np.arange(rows)[:, None] * row_len
    return pos.reshape(-1).astype(field["dtype"])
