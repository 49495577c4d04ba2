"""Command-line interface: build Trainer + strategy + module from args/YAML.

Parity with the reference's LightningCLI compatibility
(``tests/test_lightning_cli.py:11-27``): the CLI must be able to instantiate
a strategy by name from CLI arguments, resolving constructor arguments
across the strategy's own signature *and* passthrough kwargs (the reference
resolves ``RayStrategy`` ctor args against DDP kwargs like
``bucket_cap_mb``; here unknown ``--strategy.*`` keys flow into the
strategy's ``**kwargs`` the same way).

jsonargparse is not a baked-in dependency, so the parser is plain argparse
with signature introspection: every ``--trainer.X``, ``--model.X``,
``--data.X`` and ``--strategy.X`` flag maps onto the matching constructor
parameter; a ``--config file.yaml`` merges a config tree with sections
``trainer`` / ``strategy`` / ``model`` / ``data`` (CLI flags win).
"""
from __future__ import annotations

import argparse
import inspect
from typing import Any, Dict, List, Optional, Type

from ray_lightning_tpu.core.trainer import Trainer
from ray_lightning_tpu.strategies import (AllReduceStrategy, FSDPStrategy,
                                          HorovodRayStrategy, MeshStrategy,
                                          RayShardedStrategy, RayStrategy,
                                          SequenceParallelStrategy, Strategy)

#: name → class; keys are the strategies' ``strategy_name`` plus the
#: TPU-native aliases (parity: PTL's StrategyRegistry entries the reference
#: gets from ``strategy_name = "ddp_ray"`` etc.).
STRATEGY_REGISTRY: Dict[str, Type[Strategy]] = {}


def register_strategy(cls: Type[Strategy], *aliases: str) -> None:
    STRATEGY_REGISTRY[cls.strategy_name] = cls
    for a in aliases:
        STRATEGY_REGISTRY[a] = cls


register_strategy(RayStrategy, "ddp", "dp")
register_strategy(HorovodRayStrategy, "horovod", "allreduce")
if AllReduceStrategy is not HorovodRayStrategy:
    register_strategy(AllReduceStrategy)
register_strategy(RayShardedStrategy, "ddp_sharded", "zero1")
register_strategy(FSDPStrategy, "fsdp")
register_strategy(MeshStrategy, "mesh")
register_strategy(SequenceParallelStrategy, "sp", "sequence_parallel")


_TRUE = ("true", "1", "yes", "y", "on")
_FALSE = ("false", "0", "no", "n", "off")


def _parse_value(raw: str, default: Any) -> Any:
    """Coerce a CLI string to the parameter's type (inferred from default)."""
    if raw.lower() in ("null", "none"):
        return None
    if isinstance(default, bool):
        low = raw.lower()
        if low in _TRUE:
            return True
        if low in _FALSE:
            return False
        raise SystemExit(
            f"Expected a boolean (true/false), got {raw!r}")
    if isinstance(default, int):
        try:
            return int(raw)
        except ValueError:
            pass
    if isinstance(default, float):
        try:
            return float(raw)
        except ValueError:
            pass
    if isinstance(default, str):
        return raw
    if default is None:
        # untyped param: best effort — bool words, int, float, then string
        if raw.lower() in ("true", "false"):
            return raw.lower() == "true"
        for cast in (int, float):
            try:
                return cast(raw)
            except ValueError:
                continue
    return raw


def _signature_defaults(cls: type) -> Dict[str, Any]:
    out = {}
    for name, p in inspect.signature(cls.__init__).parameters.items():
        if name in ("self", "args", "kwargs"):
            continue
        out[name] = None if p.default is inspect.Parameter.empty \
            else p.default
    return out


class TpuLightningCLI:
    """Instantiate (strategy, trainer, model, datamodule) from CLI args.

    Usage::

        cli = TpuLightningCLI(MyModule, MyDataModule)
        # python train.py fit --trainer.max_epochs 3 \
        #     --strategy ddp_ray --strategy.num_workers 4 --model.lr 1e-3

    ``run=False`` only constructs the objects (the mode the parity test
    exercises, ``tests/test_lightning_cli.py:11-27``).
    """

    subcommands = ("fit", "validate", "test", "predict")

    def __init__(self,
                 model_class: type,
                 datamodule_class: Optional[type] = None,
                 args: Optional[List[str]] = None,
                 run: bool = True,
                 trainer_defaults: Optional[Dict[str, Any]] = None):
        self.model_class = model_class
        self.datamodule_class = datamodule_class
        ns, overrides = self._parse(args)
        config = self._load_config(ns.config)

        trainer_cfg = dict(trainer_defaults or {})
        trainer_cfg.update(config.get("trainer", {}))
        strategy_cfg = dict(config.get("strategy", {}))
        model_cfg = dict(config.get("model", {}))
        data_cfg = dict(config.get("data", {}))

        strategy_name = ns.strategy or strategy_cfg.pop("name", "ddp_ray")
        for section, key, raw in overrides:
            target = {
                "trainer": trainer_cfg,
                "strategy": strategy_cfg,
                "model": model_cfg,
                "data": data_cfg
            }[section]
            defaults = {
                "trainer": _signature_defaults(Trainer),
                "strategy": _signature_defaults(
                    STRATEGY_REGISTRY[strategy_name]),
                "model": _signature_defaults(model_class),
                "data": _signature_defaults(datamodule_class)
                if datamodule_class else {},
            }[section]
            target[key] = _parse_value(raw, defaults.get(key))

        self.strategy = self._instantiate_strategy(strategy_name,
                                                   strategy_cfg)
        self.trainer = Trainer(strategy=self.strategy, **trainer_cfg)
        self.model = model_class(**model_cfg)
        self.datamodule = (datamodule_class(**data_cfg)
                           if datamodule_class else None)
        self.subcommand = ns.subcommand

        if run:
            fn = getattr(self.trainer, self.subcommand)
            fn(self.model, datamodule=self.datamodule)

    # ------------------------------------------------------------------ #
    def _parse(self, args: Optional[List[str]]):
        import sys
        args = list(sys.argv[1:] if args is None else args)
        # Consume the subcommand by hand: an optional positional would
        # swallow the *value* of an unknown --section.param flag.
        subcommand = "fit"
        if args and args[0] in self.subcommands:
            subcommand = args.pop(0)
        parser = argparse.ArgumentParser(add_help=True)
        parser.add_argument("--config", default=None,
                            help="YAML config with trainer/strategy/"
                                 "model/data sections")
        parser.add_argument("--strategy", default=None,
                            help=f"one of {sorted(STRATEGY_REGISTRY)}")
        ns, rest = parser.parse_known_args(args)
        ns.subcommand = subcommand

        overrides = []
        i = 0
        while i < len(rest):
            tok = rest[i]
            if not tok.startswith("--") or "." not in tok:
                raise SystemExit(f"Unrecognized argument: {tok}")
            key = tok[2:]
            if "=" in key:
                key, raw = key.split("=", 1)
                i += 1
            else:
                if i + 1 >= len(rest):
                    raise SystemExit(f"Missing value for {tok}")
                raw = rest[i + 1]
                i += 2
            section, _, param = key.partition(".")
            if section not in ("trainer", "strategy", "model", "data"):
                raise SystemExit(
                    f"Unknown section {section!r} in {tok} (use trainer./"
                    "strategy./model./data.)")
            overrides.append((section, param, raw))
        return ns, overrides

    @staticmethod
    def _load_config(path: Optional[str]) -> Dict[str, Any]:
        if not path:
            return {}
        import yaml
        with open(path) as f:
            return yaml.safe_load(f) or {}

    @staticmethod
    def _instantiate_strategy(name: str, cfg: Dict[str, Any]) -> Strategy:
        if name not in STRATEGY_REGISTRY:
            raise SystemExit(
                f"Unknown strategy {name!r}; choose from "
                f"{sorted(STRATEGY_REGISTRY)}")
        cls = STRATEGY_REGISTRY[name]
        sig_params = set(_signature_defaults(cls))
        known = {k: v for k, v in cfg.items() if k in sig_params}
        passthrough = {k: v for k, v in cfg.items() if k not in sig_params}
        # Passthrough kwargs ride the strategy's **kwargs, the analog of
        # the reference resolving DDP kwargs like bucket_cap_mb
        # (tests/test_lightning_cli.py:15).
        return cls(**known, **passthrough)


def main(argv: Optional[List[str]] = None) -> None:
    """``python -m ray_lightning_tpu.cli --model-class pkg.Mod …``"""
    import importlib
    parser = argparse.ArgumentParser()
    parser.add_argument("--model-class", required=True,
                        help="dotted path to the TpuModule subclass")
    parser.add_argument("--datamodule-class", default=None)
    ns, rest = parser.parse_known_args(argv)

    def _resolve(path):
        mod, _, attr = path.rpartition(".")
        return getattr(importlib.import_module(mod), attr)

    TpuLightningCLI(_resolve(ns.model_class),
                    _resolve(ns.datamodule_class)
                    if ns.datamodule_class else None,
                    args=rest, run=True)


if __name__ == "__main__":
    from ray_lightning_tpu.util import enable_compile_cache
    enable_compile_cache()
    main()
