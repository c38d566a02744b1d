from .checkpointer import (CheckpointCorruptError, checkpoint_floe_graph,
                           read_floe_meta, restore_floe_graph)

__all__ = ["CheckpointCorruptError", "checkpoint_floe_graph",
           "read_floe_meta", "restore_floe_graph"]
