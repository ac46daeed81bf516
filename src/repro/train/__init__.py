from repro.train.train_step import (  # noqa: F401
    TrainState, init_train_state, make_train_setup, make_train_step,
    make_eval_step)
from repro.train.engine import (  # noqa: F401
    TrainEngine, batch_shardings, make_engine, make_shard_ctx)
from repro.train.trainer import Trainer, TrainerHooks  # noqa: F401
from repro.train.supervisor import (  # noqa: F401
    TrainSupervisor, TrainingAborted)
from repro.train.faults import (  # noqa: F401
    FaultPlan, FaultSpec, FaultyCheckpointManager, SimulatedCrash)
