"""deepspeed_tpu — a TPU-native training/inference optimization framework.

A from-scratch rebuild of the capabilities of DeepSpeed (reference v0.8.1)
on jax/XLA/pjit/shard_map/Pallas. Public surface mirrors the reference's
``deepspeed/__init__.py:14-36``: ``initialize``, ``init_inference``,
``add_config_arguments``, ``init_distributed``, ``DeepSpeedConfig``, ``zero``.
"""

from typing import Optional, Tuple

from .version import __version__
from .config import DeepSpeedConfig, load_config
from . import comm
from .comm import init_distributed
from .parallel.mesh import MeshManager, build_mesh_from_config, get_global_mesh
from .parallel.topology import (
    ProcessTopology,
    PipeDataParallelTopology,
    PipeModelDataParallelTopology,
    PipelineParallelGrid,
)
from .runtime.engine import DeepSpeedEngine
from .runtime import act_checkpoint as checkpointing  # deepspeed.checkpointing parity
from .runtime.lr_schedules import LRScheduler, build_schedule


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               mpu=None,
               dist_init_required: Optional[bool] = None,
               collate_fn=None,
               config=None,
               config_params=None,
               **kwargs) -> Tuple:
    """Wrap a model in a DeepSpeedEngine.

    Signature parity with the reference ``deepspeed.initialize``
    (deepspeed/__init__.py:52-156); returns (engine, optimizer, dataloader,
    lr_scheduler). TPU-specific extras are keyword-only: ``loss_fn``,
    ``apply_fn``, ``example_batch``, ``rng``, ``sharding_rules``,
    ``mesh_manager``.
    """
    if config is None and config_params is not None:
        config = config_params
    if config is None and args is not None:
        config = getattr(args, "deepspeed_config", None)
    if dist_init_required is None or dist_init_required:
        init_distributed()

    from .models.pipeline import PipelinedTransformer
    from .runtime.pipe.engine import PipelineEngine
    engine_cls = (PipelineEngine if isinstance(model, PipelinedTransformer)
                  else DeepSpeedEngine)
    engine = engine_cls(
        model=model,
        config=config,
        model_parameters=model_parameters,
        optimizer=optimizer,
        lr_scheduler=lr_scheduler,
        mpu=mpu,
        **kwargs)

    dataloader = None
    if training_data is not None:
        from .runtime.dataloader import DeepSpeedDataLoader
        dataloader = DeepSpeedDataLoader(
            training_data,
            batch_size=engine.config.train_batch_size,
            collate_fn=collate_fn,
            drop_last=engine.config.dataloader_drop_last)

    return engine, engine.optimizer, dataloader, engine.lr_scheduler


def init_inference(model=None, config=None, **kwargs):
    """reference: deepspeed/__init__.py:233 — build an InferenceEngine."""
    from .inference.engine import InferenceEngine
    return InferenceEngine(model=model, config=config, **kwargs)


def add_config_arguments(parser):
    """reference: deepspeed/__init__.py:159-223 — argparse flags."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed (helper flag)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to the DeepSpeed json configuration")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help=argparse_suppress())
    return parser


def argparse_suppress():
    import argparse
    return argparse.SUPPRESS




def default_inference_config():
    """reference: deepspeed/__init__.py:246 — the default inference config
    as a plain dict (feed it back to init_inference after edits)."""
    from .inference.config import DeepSpeedInferenceConfig
    return DeepSpeedInferenceConfig().model_dump()


from .models.transformer import (  # noqa: E402  (reference export names)
    DeepSpeedTransformerLayer, DeepSpeedTransformerConfig)
from .models.hf import (  # noqa: E402
    replace_transformer_layer, revert_transformer_layer)

from . import zero  # noqa: E402  (re-export; depends on runtime)
