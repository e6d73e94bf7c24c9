"""Parallelism strategies built on the primitive layer.

The reference provides the *primitives* these strategies are built from, not
the strategies themselves (SURVEY.md §2.5 maps each strategy to its
primitives). Here each is a first-class deliverable over ``tpu_mpi.xla``:

- data parallel (dp.py)      ← Allreduce of grads / Bcast of params
- tensor parallel (tp.py)    ← psum / all_gather / reduce_scatter
- sequence parallel (ring.py, ulysses.py) ← ppermute ring / all_to_all
- expert parallel (ep.py)    ← padded all_to_all with capacity masks
- pipeline parallel (pp.py)  ← ppermute microbatch rotation
- halo exchange (halo.py)    ← Cartesian ppermute of boundary slices
"""

from .dp import allreduce_grads, pmean_tree
from .tp import all_gather_output, column_parallel, row_parallel, tp_identity_fwd_psum_bwd, tp_psum_fwd_identity_bwd
from .ring import ring_attention
from .ulysses import heads_to_seq, seq_to_heads
from .ep import moe_dispatch_combine, moe_dropless
from .pp import pipeline_forward
from .halo import halo_exchange
