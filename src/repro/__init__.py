"""repro: MapReduce-based Apriori pass-fusion (Singh, Garg & Mishra 2018) on a
JAX/Pallas device mesh — frequent-itemset mining, streaming re-mining,
association-rule serving and the counting roofline.  See DESIGN.md."""

__version__ = "1.0.0"
