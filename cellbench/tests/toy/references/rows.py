"""The toy's plain reference: a table of rows from the seed, and a host
``take``."""

import numpy as np

import datagen


def make_data(cfg, seed):
    return {"table": datagen.features(cfg["rows"], cfg["row_dim"], seed,
                                      cfg["row_dtype"])}


def wrong_rows(data, ids, got):
    """How many answered rows are not the table's rows, exactly."""
    return int((np.asarray(got) != data["table"][ids]).any(axis=1).sum())
