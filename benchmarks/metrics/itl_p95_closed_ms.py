"""``itl_p95_ms`` of ``gpt2-large.batch-unshared``, under a bound of its own
(PR 31). A bound belongs to a metric, not to a cell: ``itl_p95_ms`` is
reported by all three cells and its 3% has to fit the noisiest of them, which
by the driver's runs is the sparse-expert cell (its 95th percentile moved
1.8% inside one set of six when the chip machine's host ran slow, the
open-loop cell's 1%). Batch-unshared's, a step plus a 256-token chunk round
that is nearly all device time, repeats to 0.02-0.4%, and this name holds it
to 1% (1.5% under ``itl_p95_ms`` until PR 31). Same arithmetic, same gaps."""


from harness.estimators import quantile


def read(o):
    q = quantile(o["gaps"], 0.95)
    return q and 1e3 * q["value"]
