"""Pallas TPU kernels and their dispatch (``ops/dispatch.py``).

No eager imports: ``core/env.py`` imports ``ops.dispatch`` on every env
import, and pulling a kernel module in here would drag the whole Pallas
stack (~1 s) into start-up for runs that never touch a kernel.
"""
