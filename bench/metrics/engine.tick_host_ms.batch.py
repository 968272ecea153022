"""Mean host time of an engine tick in the window (the definition and
reader of ``engine.tick_host_ms.chat``)."""
from pathlib import Path

from bench.harness import load_module

read = load_module(Path(__file__).with_name("engine.tick_host_ms.chat.py")).read
