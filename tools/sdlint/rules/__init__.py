"""Rule modules self-register into ``core.RULES`` on import."""

from . import asyncrules  # noqa: F401  SD001-SD003
from . import lockorder  # noqa: F401  SD004
from . import jaxrules  # noqa: F401  SD005-SD006
from . import telemetryrules  # noqa: F401  SD007-SD010
from . import resiliencerules  # noqa: F401  SD011
from . import journalrules  # noqa: F401  SD012
from . import autotunerules  # noqa: F401  SD013
from . import p2prules  # noqa: F401  SD014
from . import serverules  # noqa: F401  SD015
from . import flowrules  # noqa: F401  SD016
from . import commitorder  # noqa: F401  SD017
from . import frozenrules  # noqa: F401  SD018
from . import breakerrules  # noqa: F401  SD019
from . import envrules  # noqa: F401  SD021
from . import concurrency  # noqa: F401  SD023-SD026
