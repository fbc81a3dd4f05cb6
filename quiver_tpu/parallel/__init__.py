from .train import (Frontier, TrainState, call_model, make_train_step,
                    shard_batch, replicate)
from .prefetch import Prefetcher, AsyncNeighborSampler
