"""Workload generator: loads/synthesises job graphs and samples arrivals.

Counterpart of the reference's ``ddls/demands/jobs/jobs_generator.py:64``:
loads graph profile files (PipeDream ``.txt`` / CostGraphDef ``.pbtxt``) from a
directory, replicates them ``replication_factor`` times, wraps each in a
:class:`~ddls_tpu.demands.job.Job` with a sampled max-acceptable-JCT fraction,
then serves jobs (``replace`` / ``remove`` / ``remove_and_repeat``) and
interarrival times. Per-model immutable details are computed once and shared
across replicas (reference memo: jobs_generator.py:140-183).

Additions over the reference:

* ``synthetic`` config generates PipeDream-format profiles on the fly (the
  reference's datasets are not distributed with it);
* ``architecture`` config (``{config: <file>, shapes: [{seq_len,
  micro_batch}, ...]}``, and the deployment's cut ``layers`` /
  ``experts_held`` where it has one) writes one analytic profile per shape
  of a public transformer architecture (``graphs/arch.py``), one model
  name per shape;
* dataset-wide min/max stats for observation normalisation are identical in
  structure (reference: jobs_generator.py:276-333), including the
  fully-connected worst-case bound on partitioned dep totals.
"""
from __future__ import annotations

import glob
import hashlib
import os
import random
import tempfile
from typing import List, Optional, Union

import numpy as np

from ddls_tpu.demands.distributions import Distribution, make_distribution
from ddls_tpu.demands.job import Job, compute_immutable_details
from ddls_tpu.graphs import arch
from ddls_tpu.graphs.readers import read_graph_file
from ddls_tpu.graphs.synthetic import generate_pipedream_txt_files
from ddls_tpu.telemetry import startup

#: start-up gauges of an ``architecture`` job source that the fused loop
#: counts once per drained epoch trace: the sum over the bank's models of
#: ``graphs.arch.quadratic_time_share.<model>`` and the models summed
#: over, so that a window's counters give the bank's mean as a ratio;
#: then the same sums of ``branch_time_share``, ``zero_routed_share``,
#: ``index_time_share`` and ``attended_keys_share``
BANK_GAUGES = ("graphs.arch.quadratic_time_shares", "graphs.arch.models",
               "graphs.arch.branch_time_shares",
               "graphs.arch.zero_routed_shares",
               "graphs.arch.index_time_shares",
               "graphs.arch.attended_keys_shares")


def branch_time_share(graph) -> float:
    """Share of a degree-1 forward pass in ops OFF the forward graph's
    longest path by compute time: what runs beside the chain (0 on a
    chain whose only extra edges are shortcuts). From the graph's
    edges and times alone, no op name."""
    ops = graph.forward_op_ids()
    forward = set(ops)
    # the longest path ending at each forward op, and the parent it
    # comes through
    reach, before = {}, {}
    for op in graph.topo_order():
        if op not in forward:
            continue
        best = max((p for p in graph.parents(op) if p in reach),
                   key=reach.get, default=None)
        before[op] = best
        reach[op] = graph.compute_cost(op) + (
            0.0 if best is None else reach[best])
    end = max(ops, key=reach.get)
    while end is not None:
        forward.discard(end)
        end = before[end]
    return sum(graph.compute_cost(op) for op in forward) \
        / sum(graph.compute_cost(op) for op in ops)


class JobSampler:
    """Sample jobs from a pool (reference Sampler: ddls/utils.py:50).

    On pool exhaustion under ``remove_and_repeat``, the pool is rebuilt with
    fresh job ids so ids stay unique across refills.
    """

    def __init__(self, prototypes: List[Job], mode: str, shuffle: bool):
        if mode not in ("replace", "remove", "remove_and_repeat"):
            raise ValueError(f"unknown job_sampling_mode {mode}")
        self.prototypes = prototypes
        self.mode = mode
        self.shuffle = shuffle
        self.refill_counter = 0
        self._next_id = 0
        self._pool: List[Job] = []
        self._refill()

    def _refill(self) -> None:
        self._pool = []
        for proto in self.prototypes:
            self._pool.append(proto.clone_fresh(job_id=self._next_id))
            self._next_id += 1
        if self.shuffle:
            random.shuffle(self._pool)
        self.refill_counter += 1

    def __len__(self) -> int:
        return len(self._pool)

    def sample(self) -> Job:
        if not self._pool:
            raise RuntimeError(
                "job pool exhausted (job_sampling_mode='remove'); no more "
                "jobs to sample")
        idx = np.random.randint(len(self._pool))
        job = self._pool[idx]
        if self.mode == "replace":
            # hand out a fresh clone so exec state never aliases
            clone = job.clone_fresh(job_id=self._next_id)
            self._next_id += 1
            return clone
        self._pool.pop(idx)
        if self.mode == "remove_and_repeat" and not self._pool:
            self._refill()
        return job


def discover_profile_files(path_to_files: str) -> list:
    """Sorted graph-profile files under a directory — the single discovery
    rule shared by the generator and by the cluster's workload signature
    (cache validity must see exactly the files the generator loads)."""
    return sorted(
        p for p in glob.glob(path_to_files.rstrip("/") + "/*")
        if p.endswith(".txt") or p.endswith(".pbtxt"))


class JobsGenerator:
    def __init__(self,
                 path_to_files: Optional[str] = None,
                 job_interarrival_time_dist: Union[Distribution, dict] = None,
                 max_acceptable_job_completion_time_frac_dist:
                     Union[Distribution, dict, None] = None,
                 max_files: Optional[int] = None,
                 replication_factor: int = 1,
                 job_sampling_mode: str = "remove_and_repeat",
                 shuffle_files: bool = False,
                 num_training_steps: int = 1,
                 max_partitions_per_op_in_observation: int = 1,
                 synthetic: Optional[dict] = None,
                 architecture: Optional[dict] = None,
                 device_type: str = "A100",
                 **kwargs):
        if path_to_files is None and synthetic is None \
                and architecture is None:
            raise ValueError("need path_to_files, a synthetic config or an "
                             "architecture config")
        if job_interarrival_time_dist is None:
            raise ValueError(
                "job_interarrival_time_dist is required (pass a Distribution "
                "or a {'_target_': ..., **kwargs} dict)")
        self.num_training_steps = num_training_steps
        self.device_type = device_type
        self.max_files = max_files
        # config -> profiles -> OpGraphs: set-up (an env is built once per
        # run or per evaluation, never per step)
        with startup.span("startup.job_graphs"):
            graphs, dataset_id = self._load_graphs(
                path_to_files, synthetic, architecture)
        self.workload_fingerprint = (dataset_id, num_training_steps,
                                     device_type, max_files)

        self.interarrival_dist = make_distribution(job_interarrival_time_dist)
        frac_dist = make_distribution(
            max_acceptable_job_completion_time_frac_dist
            if max_acceptable_job_completion_time_frac_dist is not None
            else {"_target_": "ddls_tpu.demands.distributions.Fixed", "val": 1.0})
        sampled = frac_dist.sample()
        if isinstance(sampled, Distribution):
            # ListOfDistributions: one dist chosen per generator instance
            frac_dist = sampled
        self.frac_dist = frac_dist

        model_to_immutable = {}
        prototypes: List[Job] = []
        for _ in range(replication_factor):
            for g in graphs:
                model = g.meta["model"]
                if model not in model_to_immutable:
                    model_to_immutable[model] = compute_immutable_details(
                        g, num_training_steps)
                prototypes.append(Job(
                    graph=g,
                    num_training_steps=num_training_steps,
                    max_acceptable_jct_frac=float(self.frac_dist.sample()),
                    job_id=0,  # assigned by the sampler
                    details={"model": model},
                    immutable_details=model_to_immutable[model]))

        self.sampler = JobSampler(prototypes, job_sampling_mode, shuffle_files)
        self.max_partitions_per_op_in_observation = (
            max_partitions_per_op_in_observation)
        self.jobs_params = self._init_jobs_params(
            prototypes, max_partitions_per_op_in_observation)

    def _load_graphs(self, path_to_files, synthetic, architecture):
        """Write the profiles a ``synthetic`` or ``architecture`` config
        asks for, read every profile into an ``OpGraph`` and fingerprint
        the dataset; sets ``self.path_to_files``. Returns
        (graphs, dataset_id)."""
        generated_paths = None
        if synthetic is not None:
            path_to_files = synthetic.get("out_dir") or tempfile.mkdtemp(
                prefix="ddls_tpu_jobs_")
            kw = {k: v for k, v in synthetic.items() if k != "out_dir"}
            # use exactly the files generated this run (a reused out_dir may
            # hold stale profiles from a previous, differently-sized config)
            generated_paths = generate_pipedream_txt_files(path_to_files,
                                                           **kw)
        elif architecture is not None:
            unknown = set(architecture) - {"config", "shapes", "layers",
                                           "experts_held"}
            if unknown:
                raise ValueError(f"architecture: unknown keys {unknown}")
            arch_file = arch.load_arch_file(architecture["config"])
            # (config, shapes, the cut, the family's stated sizes)
            family = (arch.builder_config(arch_file),
                      architecture["shapes"],
                      architecture.get("layers"),
                      architecture.get("experts_held"),
                      arch_file.get("training_state"))
            path_to_files = tempfile.mkdtemp(prefix="ddls_tpu_jobs_")
            generated_paths = arch.write_profiles(path_to_files, *family)
        self.path_to_files = path_to_files

        file_paths = (sorted(generated_paths) if generated_paths is not None
                      else discover_profile_files(path_to_files))
        if not file_paths:
            raise FileNotFoundError(
                f"no .txt/.pbtxt graph profiles under {path_to_files}")
        if self.max_files is not None:
            file_paths = file_paths[:self.max_files]
        # workload fingerprint for the cluster's memo-cache validity check:
        # generated datasets are deterministic per config (seeded or
        # analytic), so the config content identifies them regardless of
        # the tmpdir they were written to; on-disk datasets fingerprint
        # exactly the files loaded (post-max_files truncation),
        # statted+digested at load time (not at reset time — the files
        # could change on disk after this generator read them)
        if synthetic is not None:
            dataset_id = ("synthetic", repr(sorted(synthetic.items())))
        elif architecture is not None:
            dataset_id = arch.dataset_id(*family)
        else:
            stats = []
            for f in file_paths:
                st = os.stat(f)
                # content digest of head+tail bytes makes the check
                # content-true: an in-place edit that preserves mtime and
                # size (some sync tools, archive extraction) still changes
                # the fingerprint and invalidates stale memo caches
                with open(f, "rb") as fh:
                    head = fh.read(4096)
                    if st.st_size > 8192:
                        fh.seek(-4096, os.SEEK_END)
                    tail = fh.read(4096)
                digest = hashlib.sha1(head + tail).hexdigest()
                stats.append((os.path.basename(f), st.st_mtime_ns,
                              st.st_size, digest))
            dataset_id = ("files", path_to_files, tuple(stats))

        graphs = [read_graph_file(p, device_type=self.device_type)
                  for p in file_paths]
        if architecture is not None:
            shares = []
            # the routed pairs that cost no expert FLOPs: the config's
            zero_experts = arch.zero_experts(family[0])
            zero_routed = arch.zero_routed_share(family[0])
            # each model's sequence length (what the learned-sparse
            # cores read of a full core's keys depends on it), and the
            # position streams a token has
            seq_len = {arch.model_name(family[0], s["seq_len"],
                                       s["micro_batch"]): int(s["seq_len"])
                       for s in family[1]}
            streams = arch.position_streams(family[0])
            for g in graphs:
                model = g.meta["model"]
                startup.set_gauge(f"graphs.arch.forward_ops.{model}",
                                  len(g.forward_op_ids()))
                startup.set_gauge(f"graphs.arch.edges.{model}", g.n_deps)
                # what the job occupies, and the most one of its deps /
                # sync-clique edges is sized by before any split
                ops = g.op_ids
                startup.set_gauge(f"graphs.arch.resident_bytes.{model}",
                                  sum(g.memory_cost(o) for o in ops))
                startup.set_gauge(f"graphs.arch.payload_bytes_max.{model}",
                                  max(g.payload(o) for o in ops))
                startup.set_gauge(
                    f"graphs.arch.sync_bytes_max.{model}",
                    max(g.sync_size(o) for o in ops
                        if not g.is_forward(o)))
                # layer kinds, and the shares of a degree-1 forward pass
                # in ops whose FLOPs grow as S^2 and in the linear
                # cores, from the profile's own op names and times
                types = g.meta["op_types"]
                for gauge, op_type in (
                        ("layers_full", "AttnCore"),
                        ("layers_window", "WindowAttnCore"),
                        ("layers_linear", "LinearAttnCore"),
                        ("layers_block_sparse", "BlockSparseAttnCore"),
                        ("layers_latent", "LatentAttnCore"),
                        ("layers_indexed", "IndexScoreTopK"),
                        ("shortcut_branches", "ShortcutCombineResidual"),
                        ("shared_expert_layers", "SharedExpert")):
                    startup.set_gauge(
                        f"graphs.arch.{gauge}.{model}",
                        sum(t == op_type for t in types.values()))

                def time_share(op_types):
                    return sum(g.compute_cost(o) for o, t in types.items()
                               if t in op_types) \
                        / sum(g.compute_cost(o) for o in types)

                share = time_share(arch.QUADRATIC_OPS)
                startup.set_gauge(
                    f"graphs.arch.quadratic_time_share.{model}", share)
                startup.set_gauge(f"graphs.arch.linear_time_share.{model}",
                                  time_share(("LinearAttnCore",)))
                # what runs beside the graph's longest path
                beside = branch_time_share(g)
                startup.set_gauge(f"graphs.arch.branch_time_share.{model}",
                                  beside)
                startup.set_gauge(f"graphs.arch.zero_experts.{model}",
                                  zero_experts)
                startup.set_gauge(f"graphs.arch.zero_routed_share.{model}",
                                  zero_routed)
                # the indexer's projections and score, the cores behind
                # it, and what it buys: keys read of a full core's
                indexer = time_share(("IndexerProj", "IndexScoreTopK"))
                startup.set_gauge(f"graphs.arch.index_time_share.{model}",
                                  indexer)
                startup.set_gauge(
                    f"graphs.arch.sparse_core_time_share.{model}",
                    time_share(("SparseAttnCore",)))
                keys = arch.attended_keys_share(family[0], seq_len[model])
                startup.set_gauge(
                    f"graphs.arch.attended_keys_share.{model}", keys)
                startup.set_gauge(f"graphs.arch.position_streams.{model}",
                                  streams)
                shares.append((share, 1, beside, zero_routed, indexer, keys))
            # the bank's mean of a share is its sum over the models
            for name, value in zip(BANK_GAUGES, map(sum, zip(*shares))):
                startup.set_gauge(name, value)
        return graphs, dataset_id

    def __len__(self) -> int:
        return len(self.sampler)

    def sample_job(self) -> Job:
        return self.sampler.sample()

    def sample_interarrival_time(self) -> float:
        if len(self.sampler) == 0:
            return float("inf")
        return float(self.interarrival_dist.sample())

    def _init_jobs_params(self, jobs: List[Job], max_parts: int) -> dict:
        """Dataset-wide normalisation stats (reference:
        jobs_generator.py:276-333). The ``max_job_total_num_*`` bounds account
        for partitioning blowing up the graph: each op can split up to
        ``max_parts`` ways; the dep-size bound assumes a fully connected
        worst case (reference: jobs_generator.py:320-324)."""
        raw = {
            "job_sequential_completion_times":
                [j.seq_completion_time for j in jobs],
            "max_acceptable_job_completion_times":
                [j.max_acceptable_jct for j in jobs],
            "max_acceptable_job_completion_time_fracs":
                [j.max_acceptable_jct_frac for j in jobs],
            "job_total_op_memory_costs":
                [j.immutable["job_total_op_memory_cost"] for j in jobs],
            "job_total_dep_sizes":
                [j.immutable["job_total_dep_size"] for j in jobs],
            "job_total_num_ops": [j.graph.n_ops for j in jobs],
            "job_total_num_deps": [j.graph.n_deps for j in jobs],
            "job_num_training_steps": [j.num_training_steps for j in jobs],
            "job_max_dep_size": [j.immutable["max_dep_size"] for j in jobs],
            "job_max_op_compute_throughputs": [
                j.immutable["max_op_compute_throughput"] for j in jobs],
        }
        params = {}
        for key, vals in raw.items():
            vals = np.asarray(vals, dtype=np.float64)
            params[f"min_{key}"] = float(vals.min())
            if key == "job_total_num_ops":
                params[f"max_{key}"] = float(vals.max() * max_parts)
            elif key == "job_total_num_deps":
                max_fwd = int((vals.max() / 2) * max_parts * 2)
                params[f"max_{key}"] = float(max_fwd + 2 * max_fwd)
            elif key == "job_total_dep_sizes":
                max_nodes = max(raw["job_total_num_ops"]) * max_parts
                fully_connected = int(max_nodes * (max_nodes - 1) / 2)
                params[f"max_{key}"] = float(vals.max() * fully_connected)
            else:
                params[f"max_{key}"] = float(vals.max())
        return params
