"""tpu-cluster-capacity: TPU-native cluster capacity analysis.

A ground-up JAX/TPU re-design of kubernetes-sigs/cluster-capacity: snapshot a
cluster into device tensors, re-express kube-scheduler filter/score plugins as
vmapped kernels, and run the greedy placement loop as a lax.scan.
"""

__version__ = "0.1.0"

from .framework import ClusterCapacity
from .models.snapshot import ClusterSnapshot
from .utils.config import SchedulerProfile, load_scheduler_config

__all__ = ["ClusterCapacity", "ClusterSnapshot", "SchedulerProfile",
           "load_scheduler_config", "__version__"]
