"""Distribution layer: sharding rules (FSDP×TP), activation policy,
gradient compression, pipeline parallelism."""
from . import act_sharding, compression, pipeline, sharding

__all__ = ["act_sharding", "compression", "pipeline", "sharding"]
