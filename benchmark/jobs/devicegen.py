"""The default job kind: PCoA of the synthetic cohort, whose genotypes the
program generates on the device from the configuration's seed
(``--ingest device``, ``VariantsPcaDriver.get_similarity_device_gen``)."""

from __future__ import annotations

from benchmark import batch, traffic


class Job:
    """One whole-genome job as a user runs it."""

    def __init__(self, cell: dict, devices, traced: bool, data) -> None:
        from spark_examples_tpu.sources.synthetic import SyntheticGenomicsSource

        cfg, trf = cell["config"], cell["traffic"]
        self.cfg, self.trf, self.devices, self.traced = cfg, trf, devices, traced
        self.source = SyntheticGenomicsSource(
            num_samples=int(cfg["num_samples"]),
            seed=int(cfg["cohort_seed"]),
            variant_spacing=int(trf["spacing"]),
            ref_block_fraction=float(cfg["ref_block_fraction"]),
            n_pops=int(cfg["n_pops"]),
        )
        self.flags = [
            "--variant-set-id", cfg["variant_set_id"],
            "--ingest", "device",
            "--block-size", str(trf["block_size"]),
            "--num-pc", str(cfg["num_pc"]),
            "--num-samples", str(cfg["num_samples"]),
            "--seed", str(cfg["cohort_seed"]),
        ] + [str(flag) for flag in cfg.get("flags", [])]

    def __call__(self, references: str) -> dict:
        from spark_examples_tpu.obs.metrics import (
            DEVICEGEN_DISPATCHES,
            INGEST_SITES_SCANNED,
        )

        record, driver = batch.pcoa(
            self, references, lambda driver, contigs: driver.get_similarity_device_gen(contigs)
        )
        record["dispatches"] = int(driver.registry.value(DEVICEGEN_DISPATCHES))
        record["sites_scanned"] = int(driver.registry.value(INGEST_SITES_SCANNED))
        return record


def warm_up(job: Job) -> None:
    """One contig of one dispatch group of the resolved length and its tail
    group (``traffic.warmup_references``)."""
    from spark_examples_tpu.ops.devicegen import auto_blocks_per_dispatch

    k = auto_blocks_per_dispatch(int(job.cfg["num_samples"]), int(job.trf["block_size"]))
    job(traffic.warmup_references(job.trf, k))
