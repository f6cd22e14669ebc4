"""TPU-native distributed-LLM training framework, built from first principles.

A brand-new framework with the capabilities of
``martin-kukla/distributed-llm-code-samples`` (analyzed in ``SURVEY.md``),
re-designed for TPU:

- **Compute path**: JAX/XLA. The model math (FFN stacks) uses hand-written
  forward/backward kernels — no autograd for the model — wrapped in
  ``jax.custom_vjp`` so the manual math *is* the differentiation rule
  (mirrors the reference's no-``nn.Module``/no-autograd stance,
  ``train_ffns.py:1-3``).
- **Parallelism**: hand-rolled over raw XLA collectives
  (``psum`` / ``all_gather`` / ``psum_scatter`` / ``ppermute``) inside
  ``jax.shard_map`` on an explicit device mesh — the TPU analogue of
  "torch.distributed as a thin wrapper over NCCL collectives".
  Strategies: single-device, DDP, FSDP/ZeRO-3, Megatron-style TP, and a
  2-D hybrid DDP x TP mesh.

Subpackages: ``ops`` (numerical core), ``models`` (parameter containers and
model families), ``parallel`` (mesh, collectives, strategies, launcher),
``data`` (deterministic seeded mock data), ``optim`` (inline SGD).
"""

__version__ = "0.1.0"

# Training hyperparameters of the reference workload (train_ffns.py:29-30).
LR = 1e-5
DLOSS_DX_COEF = 0.1
