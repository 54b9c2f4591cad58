"""Multi-tenant service plane: one long-lived shuffle daemon, many jobs.

The Exoshuffle thesis (arXiv:2203.05072) applied to this engine:
shuffle as a SHARED service rather than a per-job plugin. The pieces:

- :class:`~uda_tpu.tenant.registry.TenantRegistry` — the job/epoch
  registry with register/heartbeat/retire lifecycle, epoch fencing and
  HMAC-authenticated wire registration (``MSG_JOB``);
- :class:`~uda_tpu.tenant.sched.CreditScheduler` — weighted deficit
  round-robin over parked requests, replacing the single global
  ``mapred.rdma.wqe.per.conn`` cap with per-tenant weighted-fair
  credit flow (plus the tenant penalty box: an abusive tenant is
  deprioritized, never starved);
- per-tenant read-budget partitions in ``DataEngine`` admission and
  per-tenant ``MemoryBudget`` shares on the reduce side
  (``uda.tpu.tenant.budget.share``).

The reduce side's tenant identity (``uda.tpu.tenant.id``) is the
TASK's: each ``MergeManager`` reads it from its own config and hands it
to its segments and penalty box, which stamp it onto their hot-path
metric labels. It is not process state — the reduce tasks of one
process (a node's reduce slots) may belong to different tenants.
"""

from __future__ import annotations

from uda_tpu.tenant.registry import (DEFAULT_TENANT, TenantRecord,
                                     TenantRegistry, sign_job)
from uda_tpu.tenant.sched import CreditScheduler

__all__ = ["TenantRegistry", "TenantRecord", "CreditScheduler",
           "DEFAULT_TENANT", "sign_job"]
