from deepspeed_tpu.inference.engine import (InferenceEngine, InferenceConfig,
                                            init_inference)
from deepspeed_tpu.inference.kv_cache import (BlockAllocator,
                                              BlockPoolExhausted,
                                              InvalidBlock, blocks_for)
from deepspeed_tpu.inference.prefix_cache import PrefixCache, PrefixMatch
from deepspeed_tpu.inference.scheduler import (AdmissionRejected, Request,
                                               RequestScheduler)
from deepspeed_tpu.inference.spec_decode import (NgramProposer,
                                                 greedy_accept_len)
from deepspeed_tpu.inference.serving import (DecodeDispatchHang,
                                             SlotStateUnsupported,
                                             ResumeIncompatible,
                                             ServingConfig, ServingEngine,
                                             init_serving, load_drain_state)
from deepspeed_tpu.inference.router import (ReplicaHandle, ReplicaUnreachable,
                                            RouterConfig, ServingRouter)
