from .config import (InferenceConfig, KVQuantConfig,  # noqa: F401
                     PrefixCacheConfig, RaggedConfig, SpeculativeConfig,
                     TPConfig)
from .engine import InferenceEngine, ModelFamily, init_inference  # noqa: F401
from .engine_v2 import (InferenceEngineV2, MixedCall,  # noqa: F401
                        build_engine_v2, prompt_lookup_draft)
from .ragged import (BlockedAllocator, PrefixBlockIndex,  # noqa: F401
                     SequenceDescriptor, StateManager, UnknownSequenceError)
from .sampling import SamplingParams, sample  # noqa: F401
from .serving import (DisaggConfig, FleetConfig,  # noqa: F401
                      ReplicaRouter, Request, RequestHandle, RouterConfig,
                      SchedulerConfig, ServingScheduler, TrafficGenerator,
                      WorkloadConfig)
