"""spark_examples_tpu — a TPU-native genomics analytics framework.

A brand-new framework with the capabilities of ``googlegenomics/spark-examples``
(reference at /root/reference), redesigned TPU-first on JAX/XLA:

- Distributed datasets of genomic variants and reads streamed from a paginated
  genomics source with contig-range sharding (reference: ``rdd/VariantsRDD.scala``,
  ``rdd/ReadsRDD.scala``).
- The seven example analyses: Klotho / BRCA1 variant counting, pileup, mean
  coverage, per-base depth, tumor/normal base-frequency comparison (reference:
  ``SearchVariantsExample.scala``, ``SearchReadsExample.scala``).
- The flagship 1000 Genomes PCoA pipeline (reference: ``VariantsPca.scala``):
  genotype → similarity (Gramian) → Gower double-centering → eigendecomposition,
  rebuilt as blockwise ``G += XᵀX`` on the MXU with ``psum`` over ICI replacing
  Spark's shuffle and ``jnp.linalg.eigh`` replacing Breeze/MLlib.

Package layout:

- ``models``    — serializable Variant/Call/Read data models + builders
- ``sharding``  — contig windows, split policies, partitioners
- ``sources``   — genomics backends (synthetic, REST, local VCF/JSONL/SAM
  files with bounded-memory streaming) + client counters
- ``parallel``  — device mesh construction and the Spark-shuffle → XLA-collective mapping
- ``ops``       — device compute: gramian, centering, pca, read depth
- ``pipeline``  — datasets, stats, PCA driver, checkpointing
- ``analyses``  — the seven reference example analyses
- ``utils``     — murmur3 hashing, AF-filter arithmetic, tracing
- ``api``       — the composable public pipeline (prepare → similarity →
  center → pca), mirroring ``src/main/python/variants_pca.py:19-152``
"""

__version__ = "0.12.0"

from spark_examples_tpu.models.variant import Call, Variant, VariantKey, VariantsBuilder
from spark_examples_tpu.models.read import Read, ReadKey, ReadBuilder
from spark_examples_tpu.sharding.contig import Contig, SexChromosomeFilter
from spark_examples_tpu.sharding.partitioners import (
    FixedSplits,
    ReadsPartitioner,
    TargetSizeSplits,
    VariantsPartitioner,
)

__all__ = [
    "Call",
    "Variant",
    "VariantKey",
    "VariantsBuilder",
    "Read",
    "ReadKey",
    "ReadBuilder",
    "Contig",
    "SexChromosomeFilter",
    "VariantsPartitioner",
    "ReadsPartitioner",
    "FixedSplits",
    "TargetSizeSplits",
]
