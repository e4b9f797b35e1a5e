"""Structure generators: a configuration's ``generator`` key names the
module here, whose ``make(config) -> Structure`` builds the structure
from the configuration's own sizes and generator seed."""
