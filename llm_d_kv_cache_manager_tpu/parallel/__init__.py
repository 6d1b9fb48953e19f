from .checkpoint import (
    load_params,
    load_train_state,
    save_params,
    save_train_state,
)
from .mesh import make_mesh, MeshConfig
from .ring_attention import ring_attention, ring_attention_shard
from .sharding import param_shardings, batch_sharding, shard_params
from .train import train_step, make_train_state, loss_fn

__all__ = [
    "load_params",
    "load_train_state",
    "save_params",
    "save_train_state",
    "make_mesh",
    "MeshConfig",
    "ring_attention",
    "ring_attention_shard",
    "param_shardings",
    "batch_sharding",
    "shard_params",
    "train_step",
    "make_train_state",
    "loss_fn",
]
