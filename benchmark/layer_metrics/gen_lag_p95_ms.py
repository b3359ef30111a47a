"""Load generator: 95th percentile of how late each request was sent
(send time minus due time), over the window's requests."""

import numpy as np


def read(inputs):
    lag = inputs["window"].get("gen_lag_ms")
    return float(np.percentile(lag, 95)) if lag else None
