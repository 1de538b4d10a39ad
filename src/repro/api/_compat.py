"""Package plumbing shared by the ``__init__`` files."""


def lazy_exports(module_name: str, module_globals: dict, table: dict):
    """PEP 562 module ``__getattr__``/``__dir__`` pair over a
    ``{name: (module, attr_or_None)}`` table — shared by the package
    ``__init__`` files so heavyweight subsystems import on first touch."""

    def __getattr__(name):
        try:
            module, attr = table[name]
        except KeyError:
            raise AttributeError(
                f"module {module_name!r} has no attribute {name!r}")
        import importlib
        mod = importlib.import_module(module)
        value = mod if attr is None else getattr(mod, attr)
        module_globals[name] = value
        return value

    def __dir__():
        return sorted(set(module_globals) | set(table))

    return __getattr__, __dir__
