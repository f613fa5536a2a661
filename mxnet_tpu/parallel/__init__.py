"""``mxnet_tpu.parallel`` — SPMD scaling over device meshes.

This is the TPU-native replacement for the reference's entire distributed
stack (SURVEY.md §2.3): instead of transports (ps-lite ZMQ, NCCL rings,
Horovod/BytePS plugins — ``src/kvstore/``) there is ONE mechanism — XLA
collectives over a ``jax.sharding.Mesh`` — and parallelism strategies are
*sharding layouts*, not subsystems:

- data parallel      = batch sharded over the ``dp`` axis (allreduce ≡ psum)
- tensor parallel    = weight matrices sharded over ``tp`` (Megatron layout)
- sequence parallel  = activations sharded over ``tp`` on the time axis
  between attention/MLP blocks
- context parallel   = ring attention over ``cp`` (``ppermute`` of K/V
  blocks around the ICI ring) — the reference has NO equivalent (§5)
- ZeRO-1             = optimizer states sharded over ``dp``
  (the analog of server-side update sharding, ``kvstore_dist_server.h:346``)
- pipeline parallel  = stage-sharded ``shard_map`` microbatch loop over
  the ``pp`` axis (``mxnet_tpu.parallel.pipeline``)
"""
import time as _time

_IMPORT_T0 = _time.monotonic()    # mx.start.import, recorded below

from .mesh import (create_mesh, current_mesh, mesh_scope, local_mesh,
                   shrink_mesh, grow_mesh)
from .sharding import (P, apply_sharding_rules, kernel_shard,
                       param_sharding, shard_params, replicate)
from .train_step import TrainStep
from .ring import (ring_attention_sharded, causal_balance,
                   stripe_sequence, unstripe_sequence)
from . import pipeline
from . import seq_data
from .seq_data import SeqShardLoader, make_sequence_array, EpochPlan
from .pipeline import pipeline_apply, pipeline_vjp
from .moe import switch_moe, moe_param_specs
from .. import profiler as _profiler

_profiler.record_build_span("mx.start.import", _IMPORT_T0,
                            module=__name__)
