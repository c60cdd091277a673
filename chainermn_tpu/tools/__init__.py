"""Operational command-line tools (``python -m chainermn_tpu.tools.*``):
:mod:`~chainermn_tpu.tools.elastic` (supervised training launcher),
:mod:`~chainermn_tpu.tools.serve` (multi-replica serving),
:mod:`~chainermn_tpu.tools.fabric` (training and serving trading chips),
:mod:`~chainermn_tpu.tools.lint` (collective-correctness gate),
:mod:`~chainermn_tpu.tools.obs` (step-log summaries and traces) and
:mod:`~chainermn_tpu.tools.shardplan` (sharding-plan browser).
"""
