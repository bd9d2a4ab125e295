"""Analytic operation and byte counts, one module per model family or
kernel. Convention: a contraction of result M x N over K costs 2*M*N*K; a
training step costs three forward passes (forward, dL/dx, dL/dW).
Recomputation (remat) is never counted in a model's step."""
