from repro.serve.decode import (generate, make_decode_loop, make_prefill,
                                make_prefill_step, make_serve_step)
from repro.serve.frontend import (TrafficResult, calibrate_service_model,
                                  calibrate_service_models, serve_trace,
                                  traffic_sweep)
from repro.serve.metrics import latency_summary, padding_waste
from repro.serve.replicas import (DataParallelReplicas, ThreadPoolReplicas,
                                  make_replicas)
from repro.serve.scheduler import Batch, MicroBatchScheduler, Part
from repro.serve.traffic import (DEADLINE_CLASSES, SCENARIOS, Request, Trace,
                                 default_budgets, make_trace)
from repro.serve.vision import (BucketedViTEngine, policy_sweep,
                                vit_energy_per_image)
