"""The routed expert matmuls' least time over the grouped-matmul kernel's
device time, %, over the MoE layers only: the shared reading multiplies
one layer's work by ``num_layers``, so it is given a model section without
the leading dense layers."""

import dataclasses

import readings as rd


def read(run):
    m = run.model
    moe = dict(m, num_layers=m["num_layers"] - m.get("first_k_dense_replace", 0))
    return rd.gmm_roofline(dataclasses.replace(run, model=moe))
